"""The five readers of the scheduler's own accounting, each on a
synthetic ``Reading``: registry deltas over the window, a gap that
straddles three phases, a cell with no constrained row, and a program
that has no phase cursor (the parent commit): nothing, never a raise."""

import importlib

import pytest

from perfbench.clientlog import ClientLog
from perfbench.reading import Reading


def hist(**stages):
    return {"sutro_stage_seconds": {"series": {
        k: {"count": n, "sum": s, "buckets": {}} for k, (n, s) in stages.items()
    }}}


def counters(paths=None, rows=None):
    out = {}
    if paths is not None:
        out["sutro_sched_iterations_total"] = {"series": dict(paths)}
    if rows is not None:
        out["sutro_sched_dispatch_rows_total"] = {"series": {"": rows}}
    return out


def reading(reg0=None, reg1=None, tokens=1000, seconds=40.0, **kw):
    log = ClientLog()
    log.tokens(100.5, "j", 10, 0)
    log.tokens(100.0 + seconds - 0.5, "j", 10 + tokens, 0)
    base = dict(
        log=log, t0=100.0, t1=100.0 + seconds, startup_seconds=1.0, n_chips=1,
        device_kind="TPU v5 lite", cfg={"engine": {"decode_batch_size": 64}},
        traffic={}, reg0=reg0 or {}, reg1=reg1 or {}, spans=[], compiles=[],
        memory_peak_bytes=0,
    )
    base.update(kw)
    return Reading(**base)


def reader(name):
    return importlib.import_module(f"perfbench.layer_metrics.{name}")


NAMES = ["sched_host_share", "sched_other_share", "fsm_host_us_per_token",
         "decode_batch_occupancy", "idle_unattributed_share"]


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_cursor_reads_as_nothing(name):
    """The parent commit has only prefill / decode_window / admit /
    accept and no counter: every new reader is left out of the line."""
    before = hist(decode_window=(10, 1.0), accept=(10, 0.1))
    after = hist(decode_window=(110, 11.0), accept=(110, 1.1),
                 constraint_compile=(4, 0.2), admit=(3, 0.1))
    assert reader(name).read(reading(before, after)) is None


def test_host_and_other_shares_are_window_deltas():
    before = hist(batch_build=(10, 1.0), accept=(10, 1.0), sched_other=(1, 0.5),
                  decode_window=(10, 5.0), sched_idle=(1, 100.0))
    after = hist(
        batch_build=(110, 3.0), accept=(110, 5.0), emit=(50, 1.0),
        sched_poll=(100, 0.5), fsm_mask=(5, 0.5), sched_other=(2, 1.0),
        decode_window=(110, 25.0), prefill=(4, 2.0), sched_idle=(3, 107.0),
        flush=(5, 3.0), tokenize=(1, 9.0),
    )
    r = reading(before, after)
    # host: 2 + 4 + 1 + 0.5 + 0.5 + 0.5 = 8.5 s of 40; flush, tokenize,
    # the dispatches and the doze are not host phases
    assert reader("sched_host_share").read(r) == pytest.approx(100 * 8.5 / 40)
    # the timeline: host 8.5 + decode 20 + prefill 2 + idle 7 = 37.5
    assert reader("sched_other_share").read(r) == pytest.approx(100 * 0.5 / 37.5)


def test_fsm_host_time_per_token_and_a_cell_with_no_constrained_row():
    after = hist(fsm_mask=(8, 1.0), fsm_plan=(20, 3.0), accept=(20, 0.5),
                 constraint_compile=(64, 0.5), batch_build=(20, 0.1))
    r = reading({}, after, tokens=1000)
    assert reader("fsm_host_us_per_token").read(r) == pytest.approx(5000.0)
    # accept runs in every cell; without a mask or a plan there was no
    # constrained row, and the metric is left out rather than read as
    # "accept microseconds a token"
    plain = hist(accept=(20, 0.5), batch_build=(20, 0.1))
    assert reader("fsm_host_us_per_token").read(reading({}, plain)) is None
    silent = reading({}, after)
    silent.log.token_updates.clear()
    assert reader("fsm_host_us_per_token").read(silent) is None


def test_occupancy_counts_rows_over_non_idle_iterations():
    before = counters({"pipelined": 100, "idle": 5}, 6000)
    after = counters(
        {"pipelined": 300, "window": 10, "fastforward": 40, "idle": 5000}, 18800
    )
    r = reading(before, after)
    # 12,800 rows over (200 + 10 + 40) iterations x 64
    assert reader("decode_batch_occupancy").read(r) == pytest.approx(80.0)
    only_idle = reading(counters({"idle": 1}, 0), counters({"idle": 900}, 0))
    assert reader("decode_batch_occupancy").read(only_idle) is None


def traced(spans, gaps_s, lo_ns=5e9, mono0=200.0, seconds=20.0):
    """A reading whose trace starts at ``lo_ns`` on the trace's clock,
    which is ``mono0`` on the monotonic clock; gaps and spans are given
    in monotonic seconds."""
    to_ns = lambda t: lo_ns + (t - mono0) * 1e9  # noqa: E731
    trace = {
        "gaps_ns": [(to_ns(a), to_ns(b)) for a, b in gaps_s],
        "window_ns": (lo_ns, lo_ns + seconds * 1e9), "window_s": seconds,
        "busy_s": seconds - sum(b - a for a, b in gaps_s),
    }
    return reading(
        spans=[(n, a, b, {}) for n, a, b in spans], trace=trace,
        trace_span=(mono0, mono0 + seconds),
    )


def test_a_gap_that_straddles_three_phases_is_split_by_overlap():
    mod = reader("idle_unattributed_share")
    spans = [
        ("decode_window", 200.0, 201.0),     # device busy under it
        ("fsm_plan", 201.0, 202.5),
        ("decode_window", 202.5, 202.6),
        ("accept", 202.6, 203.0),
        ("emit", 203.0, 203.2),
        ("constraint_prep", 201.5, 201.8),   # another thread, under fsm_plan
        ("flush", 190.0, 190.5),             # long before the trace
    ]
    # one 2.3 s gap from the middle of fsm_plan to past emit, and a
    # second gap nothing covers
    r = traced(spans, [(201.2, 203.5), (210.0, 211.0)])
    parts = mod.split_by_phase(r)
    assert parts["fsm_plan"] == pytest.approx(1.3)
    assert parts["decode_window"] == pytest.approx(0.1)
    assert parts["accept"] == pytest.approx(0.4)
    assert parts["emit"] == pytest.approx(0.2)
    assert "constraint_prep" not in parts and "flush" not in parts
    assert parts["unattributed"] == pytest.approx(0.3 + 1.0)
    assert sum(parts.values()) == pytest.approx(3.3)
    assert mod.read(r) == pytest.approx(100 * 1.3 / 3.3)
    # the midpoint rule would have given all 2.3 s to fsm_plan or accept


def test_idle_share_needs_a_trace_and_gaps():
    mod = reader("idle_unattributed_share")
    assert mod.read(reading()) is None
    assert mod.read(traced([("emit", 200.0, 201.0)], [])) is None
    # no span at all (the classify cell before this PR): all of it
    assert mod.read(traced([], [(201.0, 219.0)])) == pytest.approx(100.0)
