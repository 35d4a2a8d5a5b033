"""The scheduler accounts for its own time (engine/profiling.py
StepTimer's phase cursor): on every decode path the scheduler thread's
flight-recorder spans tile the run — contiguous, never overlapping,
next to nothing left in ``sched_other`` — each path counts its own
iterations, an idle daemon adds a span a second and not one a spin,
and with telemetry off none of it exists."""

import time

import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine import profiling
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.constrain import schema_constraint_factory
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest, JobCtx
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.telemetry import doctor

ENUMS = {
    "type": "object",
    "properties": {
        "classification_result": {
            "type": "string", "enum": ["positive", "negative"],
        },
        "confidence_level": {"type": "string", "enum": ["high", "low"]},
    },
    "required": ["classification_result", "confidence_level"],
}
# a forced scaffold, then free text: the first jump engages, the
# probes inside the string fail
FREE_TEXT = {
    "type": "object",
    "properties": {"note": {"type": "string", "maxLength": 24}},
    "required": ["note"],
}
TEXTS = ["first row", "second", "third one"]


def _ecfg(**kw):
    base = dict(
        kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
        decode_batch_size=4, use_pallas=False, param_dtype="float32",
        activation_dtype="float32",
    )
    base.update(kw)
    return EngineConfig(**base)


def _requests(tok, schema=None, **kw):
    factory = schema_constraint_factory(schema, tok) if schema else None
    return [
        GenRequest(
            row_id=i, prompt_ids=np.array(tok.encode(t), np.int32),
            constraint=factory() if factory else None, **kw,
        )
        for i, t in enumerate(TEXTS)
    ]


def _idle_session(b, seconds):
    """A held-open ctx with nothing pending: the loop dozes until the
    hold drops."""
    until = time.monotonic() + seconds
    ctx = JobCtx(
        job_id="held", pending=[], on_result=lambda r: None,
        hold_open=lambda: time.monotonic() < until,
    )
    assert b.run_multi([ctx], on_job_done=lambda c, o: None) == "completed"


# path -> (engine config, schema, request kwargs, what else must show)
PATHS = {
    "pipelined": (dict(), None, dict(max_new_tokens=24, temperature=0.7), {}),
    "window": (
        dict(decode_multi_step=8, constrain_fastforward=0), ENUMS,
        dict(max_new_tokens=80, temperature=0.0), {},
    ),
    "fastforward": (
        dict(decode_multi_step=8, constrain_fastforward=16), ENUMS,
        dict(max_new_tokens=80, temperature=0.0),
        {"fsm_plan": {"engaged": True}},
    ),
    "fastforward-failed-probe": (
        dict(decode_multi_step=8, constrain_fastforward=16), FREE_TEXT,
        dict(max_new_tokens=60, temperature=0.0),
        {"fsm_plan": {"engaged": False}},
    ),
    "single": (
        dict(decode_multi_step=1), ENUMS,
        dict(max_new_tokens=80, temperature=0.0), {"fsm_mask": {}},
    ),
    # windows in flight: 1, the same path at a depth of one
    "pipelined-depth-one": (
        dict(decode_lookahead=1), None,
        dict(max_new_tokens=24, temperature=0.7), {},
    ),
    "idle": (dict(), None, None, {"sched_idle": {}}),
}
# (behind a failed probe the batch takes the window or the masked step:
# the verify forward over the opening scaffold found none of these
# random weights' unmasked tokens valid, so it is the step)
COUNTED_AS = {
    "fastforward-failed-probe": "single",
    "pipelined-depth-one": "pipelined",
}


def _scheduler_spans():
    """(name, start, end, attrs) of the scheduler thread, in order."""
    rec = telemetry.RECORDER
    out = [
        (name, t0, t0 + dur, attrs or {})
        for name, _job, t0, dur, attrs in list(rec._buf)
        if (attrs or {}).get("thread") != "prep"
    ]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("path", sorted(PATHS))
def test_phases_tile_the_scheduler_thread(path, byte_tok):
    engine_kw, schema, req_kw, must_show = PATHS[path]
    telemetry.reset_for_tests()
    assert telemetry.enabled()
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], _ecfg(**engine_kw)),
        stop_ids=byte_tok.stop_ids(),
    )
    if req_kw is None:
        before = len(telemetry.RECORDER._buf)
        _idle_session(b, 0.3)
        # ~600 spins: the doze adds at most 3 spans to the ring, the
        # session's set-up and tear-down a handful around them
        assert len(telemetry.RECORDER._buf) - before <= 3 + 9
        idle = [s for s in _scheduler_spans() if s[0] == "sched_idle"]
        assert 1 <= len(idle) <= 3
        assert sum(e - a for _n, a, e, _at in idle) >= 0.25
    else:
        done = {}
        assert b.run(
            _requests(byte_tok, schema, **req_kw),
            on_result=lambda r: done.__setitem__(r.row_id, r),
        ) == "completed"
        assert len(done) == len(TEXTS)

    spans = _scheduler_spans()
    assert spans and {s[0] for s in spans} <= set(telemetry.STAGES)
    # contiguous and non-overlapping: each phase starts where the one
    # before it ended (the cursor reads the clock once a transition)
    for (_n0, _a0, end, _x0), (name, start, _e1, _x1) in zip(spans, spans[1:]):
        assert abs(start - end) < 2e-6, (name, start - end)
    total = spans[-1][2] - spans[0][1]
    by_name = {}
    for name, a, e, _attrs in spans:
        by_name[name] = by_name.get(name, 0.0) + (e - a)
    assert sum(by_name.values()) == pytest.approx(total, abs=1e-4)
    assert by_name.get("sched_other", 0.0) < 0.05 * total
    # every span says how much of it the thread was on a CPU
    assert all("cpu_s" in attrs for _n, _a, _e, attrs in spans)

    for name, want in must_show.items():
        got = [attrs for n, _a, _e, attrs in spans if n == name]
        assert got, f"no {name} span on the {path} path"
        for key, value in want.items():
            assert any(a.get(key) == value for a in got), (name, key, got)

    series = telemetry.REGISTRY.collect()[
        "sutro_sched_iterations_total"]["series"]
    counted = COUNTED_AS.get(path, path)
    assert series.get(counted, 0) >= 1, series
    if path != "idle":
        assert "accept" in by_name and "batch_build" in by_name
        rows = telemetry.REGISTRY.collect()[
            "sutro_sched_dispatch_rows_total"]["series"][""]
        busy = sum(v for k, v in series.items() if k != "idle")
        assert 0 < rows <= busy * b.B
        # the accept spans carry the tokens they committed
        took = sum(
            attrs.get("tokens", 0) for n, _a, _e, attrs in spans
            if n == "accept"
        )
        assert took > 0
        # and the timer's summary keeps its keys, from a bounded sample
        summ = b.timer.summary()
        assert {"count", "total_s", "mean_ms", "p50_ms", "p90_ms",
                "p99_ms"} == set(summ["decode"])


def test_timer_summary_is_bounded_and_exact_in_count_and_total():
    timer = profiling.StepTimer()
    for i in range(5000):
        timer.add("decode", 0.001 * (1 + i % 10))
    stat = timer._stats["decode"]
    assert len(stat.sample) == profiling.RESERVOIR
    summ = timer.summary()["decode"]
    assert summ["count"] == 5000
    assert summ["total_s"] == pytest.approx(27.5, abs=1e-3)
    assert 1.0 <= summ["p50_ms"] <= 10.0 and summ["p99_ms"] <= 10.0


def test_cursor_folds_tails_and_leaves_nest(monkeypatch):
    """``time`` switches and restores; the sliver of the outer phase
    after it joins what follows; a ``host_leaf`` is cut out of the
    phase around it; re-entering the running phase extends it."""
    # the sliver is a few microseconds of this thread; on a loaded
    # machine it now and then outlasts the 50 us rule (2 of 25 runs),
    # and then it is a span of its own
    monkeypatch.setattr(profiling, "FOLD_S", 1e-3)
    got = []
    timer = profiling.StepTimer(
        sink=lambda *a: got.append(a), cursor=True
    )
    timer.begin()
    timer.enter("batch_build", active=2)
    with timer.time("decode"):
        time.sleep(0.002)
    timer.enter("accept")
    timer.enter("accept", tokens=3)          # same phase: one span
    with timer.host("emit"):
        timer.count("rows")
        with profiling.host_leaf("flush"):    # recorded by its caller
            time.sleep(0.002)
    timer.enter("emit")
    timer.end()
    names = [g[0] for g in got]
    assert names == [
        "sched_other", "batch_build", "decode", "accept", "emit", "emit",
    ]
    assert got[3][4] == {"tokens": 3} and got[4][4] == {"rows": 1}
    # the flush is a hole in emit, not part of it
    assert got[5][1] - (got[4][1] + got[4][2]) >= 0.002
    assert got[2][2] >= 0.002 and got[2][3] <= got[2][2]   # cpu <= wall
    assert profiling._tls.timer is None
    # outside a cursor a leaf is nothing at all
    with profiling.host_leaf("flush"):
        pass


def test_a_running_phase_shows_in_snapshots_before_it_ends(byte_tok):
    """A phase lands in the ring when it ends; until then the recorder
    shows it as an open span up to now (a 9 s plan walk must not read
    as nothing), and ``tick`` slices a long phase once a second."""
    telemetry.reset_for_tests()
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], _ecfg()),
        stop_ids=byte_tok.stop_ids(),
    )
    tm = b.timer
    tm.begin()
    tm.enter("fsm_plan", rows=4)
    time.sleep(0.02)
    last = telemetry.RECORDER.snapshot()[-1]
    assert last["name"] == "fsm_plan" and last["attrs"]["open"] is True
    assert last["dur_s"] >= 0.02
    with tm.time("decode"):
        open_now = telemetry.RECORDER.snapshot()[-1]
        assert open_now["name"] == "decode_window"
    tm.tick()                       # young segment: nothing happens
    n = len(telemetry.RECORDER._buf)
    tm._t0 -= profiling.FLUSH_S     # as if the phase were a second old
    tm.tick()
    assert len(telemetry.RECORDER._buf) == n + 1
    assert telemetry.RECORDER._buf[-1][0] == "fsm_plan"
    tm.end()
    assert not telemetry.RECORDER._open
    assert all(
        not (s.get("attrs") or {}).get("open")
        for s in telemetry.RECORDER.snapshot()
    )


def test_telemetry_off_builds_no_cursor(byte_tok, monkeypatch):
    telemetry.reset_for_tests()
    monkeypatch.setattr(telemetry, "ENABLED", False)
    made = []
    monkeypatch.setattr(
        profiling.StepTimer, "_annotate",
        lambda self, phase: made.append(phase),
    )
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], _ecfg()),
        stop_ids=byte_tok.stop_ids(),
    )
    done = {}
    b.run(
        _requests(byte_tok, None, max_new_tokens=8, temperature=0.7),
        on_result=lambda r: done.__setitem__(r.row_id, r),
    )
    assert len(done) == len(TEXTS)
    assert made == [] and len(telemetry.RECORDER._buf) == 0
    # the device-dispatch phases are timed as before, the host ones not
    assert set(b.timer.summary()) <= {"prefill", "decode", "admit_sample"}
    collected = telemetry.REGISTRY.collect()
    assert not collected["sutro_sched_iterations_total"]["series"]


def test_prep_thread_builds_are_constraint_prep(byte_tok):
    """Lazy constraints built ahead on the prep thread are
    ``constraint_prep`` spans of that thread; the scheduler thread's
    own builds stay ``constraint_compile``."""
    telemetry.reset_for_tests()
    factory = schema_constraint_factory(ENUMS, byte_tok)
    reqs = [
        GenRequest(
            row_id=i, prompt_ids=np.array(byte_tok.encode(f"row {i}"), np.int32),
            constraint_factory=factory, max_new_tokens=40, temperature=0.0,
        )
        for i in range(12)
    ]
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], _ecfg(prefill_batch_size=2)),
        stop_ids=byte_tok.stop_ids(),
    )
    done = {}
    b.run(reqs, on_result=lambda r: done.__setitem__(r.row_id, r))
    assert len(done) == 12
    spans = telemetry.RECORDER.snapshot()
    prep = [s for s in spans if s["name"] == "constraint_prep"]
    inline = [s for s in spans if s["name"] == "constraint_compile"]
    assert len(prep) + len(inline) == 12
    assert prep and all(s["attrs"]["thread"] == "prep" for s in prep)
    assert all("thread" not in s["attrs"] for s in inline)
    assert b.prep_rows_overlapped == len(prep)


def test_stage_names_agree_everywhere():
    """telemetry.STAGES, the doctor's tuples, the scheduler's quiet set
    and OBSERVABILITY.md's span table name the same stages."""
    from pathlib import Path

    from sutro_tpu.engine import scheduler

    stages = set(telemetry.STAGES)
    assert len(stages) == len(telemetry.STAGES)
    grouped = (
        set(doctor.DEVICE_STAGES) | set(doctor.HOST_STAGES)
        | set(doctor.ENVELOPE_STAGES) | set(doctor.UNCOUNTED_STAGES)
    )
    assert grouped == stages
    assert set(doctor.SCHED_HOST_STAGES) <= scheduler._QUIET_STAGES | {"accept"}
    assert set(doctor.IO_STAGES) <= set(doctor.HOST_STAGES)
    assert "sched_idle" not in doctor.HOST_STAGES
    assert scheduler._QUIET_STAGES <= stages
    assert set(scheduler._TEL_STAGE.values()) <= stages
    text = (Path(__file__).resolve().parents[1] / "OBSERVABILITY.md").read_text()
    table = text.split("<!-- span-table -->")[1]
    named = {
        line.split("|")[1].strip().strip("`")
        for line in table.splitlines()
        if line.startswith("| `")
    }
    assert named == stages


def test_doctor_names_the_largest_scheduler_phase():
    """A job whose host phases outweigh its device time is
    ``host_bound_admit`` and the evidence names the phase; idle time is
    not host time."""
    def span(name, t0, dur):
        return {"name": name, "job_id": None, "t0_s": t0, "dur_s": dur,
                "attrs": {"jobs": ["j"]}}

    doc = {
        "version": telemetry.SCHEMA_VERSION, "job_id": "j",
        "counters": {}, "spans": [
            span("sched_poll", 0.0, 0.01), span("fsm_plan", 0.01, 2.0),
            span("decode_window", 2.01, 0.2), span("accept", 2.21, 0.5),
            span("emit", 2.71, 0.1), span("sched_idle", 2.81, 9.0),
        ],
    }
    verdict = doctor.diagnose(doc)
    assert verdict["verdict"] == "host_bound_admit"
    assert any("fsm_plan" in line for line in verdict["evidence"])
    att = doctor._attribution(doc["spans"])
    assert att["host_s"] == pytest.approx(2.61)
    # with the idle span as the only non-device time it is healthy
    doc["spans"] = [span("decode_window", 0.0, 1.0),
                    span("sched_idle", 1.0, 9.0)]
    assert doctor.diagnose(doc)["verdict"] != "host_bound_admit"
