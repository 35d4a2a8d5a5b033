"""The admission wave (engine/scheduler.py ``_admit_batch`` /
``_resolve_wave``): a row's prefill and its first-token sample are
dispatched with nothing waited for, and the first tokens of everything
an iteration admitted come back in ONE host sync. A job that streams its
tokens cuts the wave at its own row, which is how these tests resolve a
wave row by row: every output of a wave must be bit-equal to that, on a
dense, a routed, a state-slot and a window-pool model, and whatever
releases or moves a slot must find every row armed. Where the wave's
rows can enter a fused window by their first tokens on the device, the
window goes out first and the wave is resolved behind it: same tokens,
and nothing pending across an iteration's end."""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import (
    ContinuousBatcher, GenRequest, JobCtx, _admit_sample_jit, _step_seed,
)
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.telemetry import doctor

PS, MP, B, CHUNK = 8, 16, 8, 32
PRESETS = {
    "dense": "tiny-dense",
    "routed": "tiny-lfm2",
    "state-slot": "tiny-granite",
    "window-pool": "tiny-mellum2",
    # generates by blocks of 4: admission samples no first token
    "block": "tiny-sdar",
}
WAVE_PRESETS = [p for p in PRESETS if p != "block"]
TEXTS = [
    "hello", "a second row", "third", "the fourth of eight rows",
    # past prefill_chunk: it is admitted by _prefill_tick, a chunk an
    # iteration, and its last chunk joins that iteration's wave
    "row five is longer than one prefill chunk of thirty-two tokens",
    "six", "seven, nearly there", "eight",
]
WAVES = "sutro_admit_waves_total"
WAVE_ROWS = "sutro_admit_wave_rows_total"
JOINED = "sutro_admit_wave_joined_rows_total"
AHEAD = "sutro_decode_ahead_windows_total"


def _ecfg(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=B,
        max_model_len=PS * MP, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=CHUNK,
        decode_multi_step=4, seed=5,
    )
    base.update(kw)
    return EngineConfig(**base)


@functools.lru_cache(maxsize=None)
def runner_of(preset: str, batch: int = B) -> ModelRunner:
    mcfg = MODEL_CONFIGS[PRESETS[preset]]
    ecfg = _ecfg(decode_batch_size=batch)
    r = ModelRunner(mcfg, ecfg, num_pages=1 + batch * MP)
    if preset == "window-pool":
        # a window pool of its own size: pages are bound and given back
        r = ModelRunner(
            mcfg, ecfg, params=r.params, num_pages=1 + batch * MP,
            window_pages=1 + batch * r.window_span,
        )
        assert r.pools.window is not None
    return r


def tok_of(runner) -> ByteTokenizer:
    return ByteTokenizer(vocab_size=runner.mcfg.vocab_size)


@pytest.fixture(autouse=True)
def _telemetry_on():
    before = telemetry.ENABLED
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(before)


def batcher(runner, pbs=1, stop_ids=(), **kw) -> ContinuousBatcher:
    b = ContinuousBatcher(runner, stop_ids=list(stop_ids), seed=3, **kw)
    # prefill_batch_size is the scheduler's alone: one runner serves both
    b.ecfg = dataclasses.replace(b.ecfg, prefill_batch_size=pbs)
    return b


def requests(tok, texts=TEXTS, seeded=False, **kw):
    kw.setdefault("max_new_tokens", 9)
    out = []
    for i, t in enumerate(texts):
        sampled = dict(temperature=0.8, top_p=0.9) if i % 3 else {}
        out.append(GenRequest(
            row_id=i, prompt_ids=np.array(tok.encode(t), np.int32),
            row_seed=(11 + i) if seeded and i != 2 else None,
            **{**dict(temperature=0.0), **sampled, **kw},
        ))
    return out


def counter(name: str, key=None) -> float:
    series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
    if key is not None:
        return sum(v for k, v in series.items() if key in str(k))
    return sum(series.values())


def joined():
    """(rows that entered their first window by the token on the
    device, rows armed on the host first) so far."""
    return np.array([counter(JOINED, "device"), counter(JOINED, "host")])


def ahead():
    """Windows asked for ahead of one in flight so far: (sent, held
    because no row could use one, held for the rows ending in flight)."""
    return np.array([counter(AHEAD, k)
                     for k in ("sent", "held_unused", "held_ending")])


def run(b, reqs, stream=False, **ctx_kw):
    """One job through ``run_multi``; ``stream`` gives it an ``on_token``
    hook, which resolves every dispatch at once (today's row by row).
    Returns ({row: (reason, tokens, logprob)}, waves, wave rows)."""
    res, streamed = {}, []
    ctx = JobCtx(
        job_id="wave", pending=list(reqs),
        on_result=lambda r: res.__setitem__(r.row_id, r),
        on_token=(lambda *a: streamed.append(a)) if stream else None,
        **ctx_kw,
    )
    w0, r0 = counter(WAVES), counter(WAVE_ROWS)
    run.ctx = ctx
    state = b.run_multi([ctx], on_job_done=lambda c, o: None)
    assert state == "completed"
    out = {
        i: (r.finish_reason, list(r.token_ids), r.cumulative_logprob)
        for i, r in res.items()
    }
    return out, counter(WAVES) - w0, counter(WAVE_ROWS) - r0


def all_free(b, runner, free0):
    assert b.free_page_count == free0
    assert all(s is None for s in b.slots) and not b._wave
    if runner.pools.slots is not None:
        assert runner.pools.slots.in_use == 0


def prefill_spans():
    return [
        s for s in telemetry.RECORDER.snapshot() if s["name"] == "prefill"
    ]


# -- a wave gives what row by row gives ---------------------------------------

@pytest.mark.parametrize("seeded", [False, True], ids=["unseeded", "seeded"])
@pytest.mark.parametrize("pbs", [1, 8], ids=["batch1", "batch8"])
@pytest.mark.parametrize("preset", WAVE_PRESETS)
def test_a_wave_gives_what_row_by_row_gives(preset, pbs, seeded):
    runner = runner_of(preset)
    tok = tok_of(runner)
    telemetry.reset_for_tests()
    telemetry.set_enabled(True)
    held = "sutro_moe_routed_rows_total"

    b = batcher(runner, pbs)
    free0, h0 = b.free_page_count, counter(held)
    wave, waves, rows = run(b, requests(tok, seeded=seeded))
    routed_wave = counter(held) - h0
    spans = prefill_spans()
    all_free(b, runner, free0)

    b = batcher(runner, pbs)
    h0 = counter(held)
    by_row, waves_s, rows_s = run(b, requests(tok, seeded=seeded), stream=True)
    all_free(b, runner, free0)

    assert set(wave) == set(range(len(TEXTS)))
    assert wave == by_row  # tokens, log-probabilities, finish reasons
    # every row is armed by exactly one wave; a wave is a host sync
    assert rows == rows_s == len(TEXTS)
    # (eight a dispatch: the seven short rows, then the long one's
    # last chunk, either way)
    assert waves == 2 and waves_s == (2 if pbs == 8 else len(TEXTS))
    # every prefill span says its tokens, and they add up; the waves'
    # resolving spans say their rows
    assert all("tokens" in s["attrs"] for s in spans)
    assert sum(s["attrs"]["tokens"] for s in spans) == sum(
        len(r.prompt_ids) for r in requests(tok)
    )
    assert sum(s["attrs"].get("wave_rows", 0) for s in spans) == len(TEXTS)
    if preset == "routed":
        assert routed_wave == counter(held) - h0 > 0
        got = [s["attrs"] for s in spans if "experts_touched" in s["attrs"]]
        assert got and all(a["expert_rows_held"] > 0 for a in got)
        # the counts come back with the wave: on its resolving span
        assert all("wave_rows" in a for a in got)


def test_a_long_row_joins_the_wave_of_its_last_chunk():
    runner = runner_of("dense")
    tok = tok_of(runner)
    texts = [TEXTS[0], TEXTS[4], TEXTS[2]]
    n_long = len(tok.encode(TEXTS[4]))
    assert CHUNK < n_long <= 2 * CHUNK
    wave, waves, rows = run(batcher(runner), requests(tok, texts))
    by_row, waves_s, _ = run(batcher(runner), requests(tok, texts), stream=True)
    assert wave == by_row
    # the two short rows in one wave; the long row's first chunk is
    # waited for by nobody, its second brings its first token
    assert (waves, rows) == (2, 3)
    assert waves_s == 3


@pytest.mark.parametrize("pbs", [1, 4], ids=["batch1", "batch4"])
def test_rows_behind_a_shared_prefix_start_past_it(pbs):
    runner = runner_of("dense")
    tok = tok_of(runner)
    shell = "You are a terse classifier. Decide the sentiment of: "
    texts = [shell + t for t in ("great!", "bad movie", "meh", "the worst")]
    b = batcher(runner, pbs)
    wave, waves, rows = run(b, requests(tok, texts, seeded=True))
    paid = b.prefill_tokens
    by_row, _, _ = run(batcher(runner, pbs), requests(tok, texts, seeded=True),
                       stream=True)
    assert wave == by_row and len(wave) == 4
    shared = (len(tok.encode(shell)) // PS) * PS
    # the shell once, then each row from ``start`` = the shared pages
    assert paid == shared + sum(len(tok.encode(t)) - shared for t in texts)
    assert (waves, rows) == (1, 4)


# -- the first token: the tree before, a mask, a stop id ----------------------

def test_first_tokens_are_what_host_padded_logits_sampled():
    """The tree before this one fetched the rows' logits, padded them
    with zero rows on the host and sampled; the wave samples the
    program's own bucket where it lies. Same first token, same
    log-probability, to the bit."""
    runner = runner_of("dense")
    tok = tok_of(runner)
    # shortest first, as admission takes them: the batch's row order
    reqs = [
        dataclasses.replace(r, row_seed=21 + i)
        for i, r in enumerate(requests(
            tok, ["a", "bb b", "ccc cc c"], max_new_tokens=1,
            temperature=0.8, top_p=0.9,
        ))
    ]
    got, _, _ = run(batcher(runner, pbs=4), reqs)

    tables = np.zeros((3, MP), np.int32)
    tables[:, 0] = [1, 2, 3]
    logits = runner.prefill_batch([r.prompt_ids for r in reqs], tables)
    pad = np.zeros((1, logits.shape[1]), logits.dtype)
    seeds = [_step_seed(r.row_seed, 0) for r in reqs] + [0]
    t, lp, _, _ = _admit_sample_jit(
        np.concatenate([logits, pad]), jax.random.PRNGKey(3),
        np.array([0.8] * 3 + [0.0], np.float32),
        np.array([0.9] * 3 + [1.0], np.float32),
        np.zeros((4,), np.int32), None, np.array(seeds, np.int32),
    )
    for i in range(3):
        assert got[i][1] == [int(t[i])]
        assert got[i][2] == float(lp[i])


class _OnlyFiveOrSix:
    """A constraint that allows two ids, counts what it is asked and
    told, and is complete after three tokens."""

    def __init__(self, vocab):
        self.vocab, self.asked, self.told = vocab, 0, []

    def allowed_tokens(self):
        self.asked += 1
        m = np.zeros((self.vocab,), bool)
        m[[5, 6]] = True
        return m

    def advance(self, token_id):
        self.told.append(int(token_id))

    def is_complete(self):
        return len(self.told) >= 3


@pytest.mark.parametrize("stream", [False, True], ids=["wave", "row-by-row"])
def test_a_constrained_first_token_is_masked_and_advances_once(stream):
    runner = runner_of("dense")
    tok = tok_of(runner)
    fsms = [_OnlyFiveOrSix(runner.mcfg.vocab_size) for _ in range(3)]
    reqs = [
        dataclasses.replace(r, constraint=c, temperature=0.0)
        for r, c in zip(requests(tok, TEXTS[:3]), fsms)
    ]
    out, waves, _ = run(batcher(runner), reqs, stream=stream)
    assert waves == (3 if stream else 1)
    for i, c in enumerate(fsms):
        reason, tokens, _ = out[i]
        assert reason == "schema_complete" and len(tokens) == 3
        assert set(tokens) <= {5, 6}
        # told every token once, the first included, in order
        assert c.told == tokens


def test_a_first_token_that_stops_ends_the_row_in_its_iteration():
    runner = runner_of("dense")
    tok = tok_of(runner)
    greedy, _, _ = run(batcher(runner), requests(tok, TEXTS[:1]))
    stop = greedy[0][1][0]
    b = batcher(runner, stop_ids=[stop])
    out, waves, rows = run(b, requests(tok, TEXTS[:1]))
    assert out[0][:2] == ("stop", []) and (waves, rows) == (1, 1)
    # a plain row: its window went out first, the resolve behind it
    # ended the row, and the window's steps for it committed nothing
    st = run.ctx.stats
    assert st["out"] == 1 and st["lost_stale"] == st["row_steps"] > 0
    # a row that can only end on its first token waits for no window
    b = batcher(runner)
    out, _, _ = run(b, requests(tok, TEXTS[:1], max_new_tokens=1))
    assert out[0][:2] == ("length", [stop])
    assert "decode" not in b.timer.summary()


# -- how often the host waits --------------------------------------------------

@pytest.mark.parametrize("stream", [False, True], ids=["batch-job", "streams"])
def test_one_host_sync_a_wave_and_one_a_row_for_a_job_that_streams(stream):
    runner = runner_of("dense")
    tok = tok_of(runner)
    telemetry.reset_for_tests()
    telemetry.set_enabled(True)
    _, waves, rows = run(batcher(runner), requests(tok, TEXTS[:4]),
                         stream=stream)
    assert (waves, rows) == ((4, 4) if stream else (1, 4))
    resolving = [s["attrs"] for s in prefill_spans()
                 if "wave_rows" in s["attrs"]]
    assert [a["wave_rows"] for a in resolving] == (
        [1, 1, 1, 1] if stream else [4]
    )
    for a in resolving:
        assert a["tokens"] == 0 and a["wave_tokens"] > 0 and a["wave_s"] > 0


def test_the_doctor_grades_a_wave_not_its_dispatches():
    device = {"device_kind": "TPU v5 lite", "n_devices": 1,
              "n_params": 4_000_000_000}

    def span(dur, **attrs):
        return {"name": "prefill", "t0_s": 0.0, "dur_s": dur, "attrs": attrs}

    wave = [span(0.001, tokens=800, wave=3), span(0.001, tokens=800, wave=3),
            span(0.2, tokens=0, wave=3, wave_rows=2, wave_tokens=1600,
                 wave_s=0.25)]
    alone = [span(0.25, tokens=1600)]
    got = doctor._grade_roofline(wave, device, {})
    assert got["mfu_prefill_median"] == doctor._grade_roofline(
        alone, device, {}
    )["mfu_prefill_median"] > 0


def test_the_two_series_have_their_doc_rows():
    doc = (Path(__file__).parent.parent / "OBSERVABILITY.md").read_text()
    for name in (WAVES, WAVE_ROWS, JOINED, AHEAD):
        assert f"| `{name}` | counter |" in doc
    assert "`held_unused`" in doc and "`held_ending`" in doc
    assert "`wave_rows`" in doc and "`joined_device`" in doc
    assert "`device`" in doc and "`host`" in doc


# -- what lands in the middle of a wave ---------------------------------------

@pytest.mark.parametrize("k", [1, 3], ids=["first-row", "third-row"])
def test_a_dispatch_that_raises_arms_the_rows_before_it(k, monkeypatch):
    runner = runner_of("state-slot")
    tok = tok_of(runner)
    b = batcher(runner)
    free0 = b.free_page_count
    calls, real = [], runner.prefill

    def failing(*a, **kw):
        calls.append(1)
        if len(calls) == k:
            raise RuntimeError("no such device")
        return real(*a, **kw)

    monkeypatch.setattr(runner, "prefill", failing)
    try:
        with pytest.raises(RuntimeError, match="no such device"):
            run(b, requests(tok, TEXTS[:4]))
        armed = [s for s in b.slots if s is not None]
        # k - 1 rows armed with their first token, as row by row left
        # them; the failed row and the rows behind it hold nothing
        assert len(armed) == k - 1 and not b._wave
        assert all(len(s.out_ids) == 1 and s.last_token == s.out_ids[0]
                   for s in armed)
        assert runner.pools.slots.in_use == k - 1
        assert free0 - b.free_page_count == sum(len(s.pages) for s in armed)
    finally:
        for i, s in enumerate(b.slots):
            if s is not None:
                b._drop_slot(i)
    all_free(b, runner, free0)


def _mid_wave(b, then):
    """Run ``then(order)`` once, between ``_admit_pending`` and the
    wave's resolve (where ``_prep_pump`` is called): the slots of the
    rows just dispatched are still pending."""
    pump, done = b._prep_pump, []

    def pumped(order):
        if b._wave and not done:
            done.append(len(b._wave))
            then(order)
        return pump(order)

    b._prep_pump = pumped
    return done


def test_a_cancel_that_lands_mid_wave_finds_every_row_armed():
    runner = runner_of("state-slot")
    tok = tok_of(runner)
    greedy, _, _ = run(batcher(runner), requests(tok, TEXTS[:3], temperature=0.0))
    b = batcher(runner)
    free0 = b.free_page_count
    ended = []
    landed = _mid_wave(b, lambda order: b._finish_job(
        order[0], "cancelled", lambda c, o: ended.append(o), emit_cancel=True
    ))
    out, waves, rows = run(b, requests(tok, TEXTS[:3], temperature=0.0))
    assert landed == [3] and ended == ["cancelled"]
    assert (waves, rows) == (1, 3)
    for i in range(3):
        # armed by the cancel's own resolve, then emitted as cancelled
        assert out[i][:2] == ("cancelled", greedy[i][1][:1])
    all_free(b, runner, free0)


def test_a_yield_that_lands_mid_wave_drops_armed_rows_and_leaks_nothing():
    runner = runner_of("state-slot")
    tok = tok_of(runner)
    b = batcher(runner)
    free0 = b.free_page_count
    landed = _mid_wave(b, lambda order: b._suspend_job(order[0]))
    out, waves, rows = run(b, requests(tok, TEXTS[:3]))
    # the rows were armed, then dropped with no result (they regenerate
    # when the job is resumed)
    assert landed == [3] and out == {} and (waves, rows) == (1, 3)
    all_free(b, runner, free0)


def test_an_eviction_mid_wave_chooses_among_armed_rows():
    """Two of four slots hold a running job's rows; a second job's two
    rows are dispatched into the other two, and before their first
    tokens are back a chat finds the batch full. The eviction resolves
    the wave first: its victim is an ARMED row (the cheapest: one just
    admitted), which regenerates, and every row ends as it does alone."""
    runner = runner_of("dense", 4)
    tok = tok_of(runner)
    long_run = dict(max_new_tokens=40, temperature=0.0)
    short = dict(max_new_tokens=6, temperature=0.0)

    def alone(texts, **kw):
        return run(batcher(runner), requests(tok, texts, **kw))[0]

    want = {"a": alone(TEXTS[:2], **long_run),
            "b": alone(TEXTS[2:4], **short),
            "c": alone(TEXTS[5:6], **short)}

    b = batcher(runner)
    b.ecfg = dataclasses.replace(b.ecfg, interactive_slots=1)
    free0 = b.free_page_count
    res = {"a": {}, "b": {}, "c": {}}

    def ctx(name, reqs, **kw):
        return JobCtx(
            job_id=name, pending=list(reqs),
            on_result=lambda r: res[name].__setitem__(
                r.row_id, (r.finish_reason, list(r.token_ids),
                           r.cumulative_logprob)),
            **kw,
        )

    ja = ctx("a", requests(tok, TEXTS[:2], **long_run))
    jb = ctx("b", requests(tok, TEXTS[2:4], **short), priority=-2)
    jc = ctx("c", requests(tok, TEXTS[5:6], **short), priority=-1,
             interactive=True, on_token=lambda *x: None)
    later, polls = [jb, jc], []

    def poll_new():
        polls.append(1)
        # once job a's rows are decoding: b, then the chat, in one poll
        return later.pop(0) if len(polls) > 3 and later else None

    waves0 = counter(WAVES)
    state = b.run_multi([ja], on_job_done=lambda c, o: None, poll_new=poll_new)
    assert state == "completed" and not later
    assert res == want
    # the victim was one of b's rows, armed by the eviction's resolve
    assert jb.stats.get("preempted") == 1 and "preempted" not in ja.stats
    # a's rows; b's two, cut by the eviction; the chat; b's row again
    assert counter(WAVES) - waves0 == 4
    all_free(b, runner, free0)


# -- the window goes out first, the wave is resolved behind it ----------------

def turnover(tok, n=2 * B, **kw):
    """``n`` plain rows of staggered lengths over fewer slots: rows end
    in different windows, so waves are admitted with windows in flight."""
    reqs = requests(tok, [TEXTS[i % len(TEXTS)] for i in range(n)], **kw)
    return [
        dataclasses.replace(r, max_new_tokens=5 + 4 * (i % 3))
        for i, r in enumerate(reqs)
    ]


@pytest.mark.parametrize("pbs", [1, 8], ids=["batch1", "batch8"])
@pytest.mark.parametrize("preset", list(PRESETS))
def test_a_wave_behind_its_window_gives_what_row_by_row_gives(preset, pbs):
    runner = runner_of(preset, 16 if preset == "block" else B)
    tok = tok_of(runner)
    n = 3 * B
    telemetry.reset_for_tests()
    telemetry.set_enabled(True)
    b = batcher(runner, pbs)
    free0, j0 = b.free_page_count, joined()
    wave, _, rows = run(b, turnover(tok, n))
    on_device, on_host = joined() - j0
    spans = [s["attrs"] for s in prefill_spans() if "wave_rows" in s["attrs"]]
    all_free(b, runner, free0)

    b = batcher(runner, pbs)
    j0 = joined()
    by_row, _, rows_s = run(b, turnover(tok, n), stream=True)
    all_free(b, runner, free0)

    assert set(wave) == set(range(n))
    assert wave == by_row  # tokens, log-probabilities, finish reasons
    assert rows == rows_s == n == on_device + on_host
    # the waves of plain rows entered a window unread, the first into
    # an empty machine and the later ones with windows in flight (but
    # where a row near its pages' end left no room for a window to go
    # out: those were armed behind no dispatch); the job that streams
    # armed every row on the host first
    assert on_device > n // 2 and tuple(joined() - j0) == (0, n)
    assert sum(a["joined_device"] for a in spans) == on_device


def test_a_first_token_that_stops_behind_its_window_is_counted_stale():
    runner = runner_of("dense")
    tok = tok_of(runner)
    greedy, _, _ = run(batcher(runner), requests(tok, TEXTS[:4], temperature=0.0))
    stop = greedy[1][1][0]  # row 1 ends on its first token
    kw = dict(temperature=0.0)
    b = batcher(runner, stop_ids=[stop])
    j0 = joined()
    out, waves, rows = run(b, requests(tok, TEXTS[:4], **kw))
    stats = dict(run.ctx.stats)
    assert tuple(joined() - j0) == (4, 0) and (waves, rows) == (1, 4)
    by_row, _, _ = run(batcher(runner, stop_ids=[stop]),
                       requests(tok, TEXTS[:4], **kw), stream=True)
    assert out == by_row and out[1][:2] == ("stop", [])
    # ended by the resolve of its own iteration; the windows that held
    # it (two go out into an empty machine) lost its steps as stale,
    # which the row-by-row order never dispatched
    ended = [i for i, r in out.items() if r[1] == []]
    assert stats["lost_stale"] == 2 * 4 * len(ended) > 0
    assert run.ctx.stats.get("lost_stale", 0) == 0


def test_a_wave_fetch_that_raises_behind_the_window_leaks_nothing(monkeypatch):
    runner = runner_of("state-slot")
    tok = tok_of(runner)
    b = batcher(runner)
    free0 = b.free_page_count
    real, seen = jax.device_get, []

    def failing(x):
        if isinstance(x, list) and x and isinstance(x[0], tuple):
            # the wave's fetch: by now the window holds its rows
            seen.append(sum(s is not None and s.first_pending for s in b.slots))
            raise RuntimeError("the device went away")
        return real(x)

    monkeypatch.setattr(jax, "device_get", failing)
    res = {}
    ctx = JobCtx(job_id="lost", pending=requests(tok, TEXTS[:4]),
                 on_result=lambda r: res.__setitem__(r.row_id, r))
    with pytest.raises(RuntimeError, match="went away"):
        b.run_multi([ctx], on_job_done=lambda c, o: None)
    monkeypatch.undo()
    # all four were seated and in the window; none was armed, every one
    # was given up with its pages, slot and state, and no token of the
    # window reached a result
    assert seen == [4] and res == {} and ctx.n_slots == 0
    all_free(b, runner, free0)
    # the batcher is whole: the same rows run again on it
    kw = dict(temperature=0.0)
    again, _, _ = run(b, requests(tok, TEXTS[:4], **kw))
    assert again == run(batcher(runner), requests(tok, TEXTS[:4], **kw))[0]
    all_free(b, runner, free0)


@pytest.mark.parametrize("what", ["cancel", "yield", "eviction"])
def test_the_next_iteration_finds_no_pending_slot(what):
    """A slot is pending from its prefill's dispatch to the resolve
    behind its iteration's decode dispatch and across nothing else: what
    releases or moves slots at the top of the next iteration (a cancel,
    a yield) or inside its admission (an eviction) finds every row
    armed."""
    runner = runner_of("dense", 4)
    tok = tok_of(runner)
    b = batcher(runner)
    b.ecfg = dataclasses.replace(b.ecfg, interactive_slots=1)
    free0, j0 = b.free_page_count, joined()
    looks, res = [], {}

    def unarmed():
        return sum(
            s is not None and not s.prefilling
            and (s.first_pending or not s.out_ids) for s in b.slots
        )

    def look():
        looks.append((len(b._wave), unarmed()))
        return len(looks)

    def ctx(name, reqs, **kw):
        return JobCtx(job_id=name, pending=list(reqs), on_result=lambda r:
                      res.__setitem__((name, r.row_id), r.finish_reason), **kw)

    rows = turnover(tok, 8, temperature=0.0)
    if what == "eviction":
        # every slot held for long: the chat has to take one
        rows = requests(tok, TEXTS[:4], max_new_tokens=30, temperature=0.0)
    ja = ctx("a", rows, should_cancel=(
        (lambda: look() > 4) if what == "cancel" else (lambda: look() < 0)
    ))
    chat = ctx("c", requests(tok, TEXTS[5:6], max_new_tokens=4),
               priority=-1, interactive=True, on_token=lambda *x: None)
    later = [chat] if what == "eviction" else []
    evict = b._evict_for_interactive

    def evicting(c):
        freed = evict(c)
        if c.interactive:
            # it resolved the wave in front of it before it chose
            looks.append((len(b._wave), unarmed()))
        return freed

    b._evict_for_interactive = evicting
    state = b.run_multi(
        [ja], on_job_done=lambda c, o: None,
        poll_new=lambda: later.pop(0) if len(looks) > 3 and later else None,
        should_yield=(lambda: len(looks) > 4) if what == "yield" else None,
    )
    assert state == ("yielded" if what == "yield" else "completed")
    assert len(looks) > 4 and set(looks) == {(0, 0)}
    assert (joined() - j0)[0] >= 4  # the waves were held, and resolved
    if what == "cancel":
        assert set(res.values()) <= {"cancelled", "length", "stop"}
        assert "cancelled" in res.values()
    if what == "eviction":
        assert ja.stats.get("preempted") == 1 and len(res) == 5
    all_free(b, runner, free0)


class _Anything:
    """A constraint that allows every id and never completes."""

    def __init__(self, vocab):
        self.vocab = vocab

    def allowed_tokens(self):
        return np.ones((self.vocab,), bool)

    def advance(self, token_id):
        pass

    def is_complete(self):
        return False


@pytest.mark.parametrize("how", ["plain", "constrained", "streams", "seeded",
                                 "one-token"])
def test_what_the_batch_shows_decides_where_the_wave_is_resolved(how):
    runner = runner_of("dense")
    tok = tok_of(runner)
    reqs = requests(tok, TEXTS[:4], temperature=0.0)
    if how == "constrained":
        # one such row in the wave: the whole wave resolves first
        reqs[2] = dataclasses.replace(
            reqs[2], constraint=_Anything(runner.mcfg.vocab_size)
        )
    elif how == "seeded":
        reqs[1] = dataclasses.replace(reqs[1], row_seed=7, temperature=0.8)
    elif how == "one-token":
        reqs[3] = dataclasses.replace(reqs[3], max_new_tokens=1)
    b = batcher(runner)
    order, resolve, build = [], b._resolve_wave, b._build_batch
    b._resolve_wave = lambda *a: (order.append("resolve")
                                  if b._wave else None, resolve(*a))[1]
    b._build_batch = lambda act: (order.append("build"), build(act))[1]
    j0 = joined()
    run(b, reqs, stream=how == "streams")
    on_device, on_host = joined() - j0
    if how == "plain":
        assert (on_device, on_host) == (4, 0)
        assert order[:2] == ["build", "resolve"]
    else:
        assert (on_device, on_host) == (0, 4)
        assert order[0] == "resolve"


def test_a_plain_wave_into_a_constrained_batch_is_resolved_before_its_step():
    """The wave's own rows are plain, the batch they join is not: the
    build shows it (``_choose_path`` does not answer ``pipelined``), the
    wave is resolved and the batch built anew."""
    runner = runner_of("dense", 4)
    tok = tok_of(runner)
    fsm = dataclasses.replace(
        requests(tok, TEXTS[:1], temperature=0.0, max_new_tokens=20)[0],
        constraint=_Anything(runner.mcfg.vocab_size),
    )
    plain = [dataclasses.replace(r, row_id=1 + i) for i, r in enumerate(
        requests(tok, TEXTS[1:3], temperature=0.0, max_new_tokens=6))]
    want = run(batcher(runner), plain)[0]
    res = {}

    def ctx(name, reqs):
        return JobCtx(job_id=name, pending=list(reqs), on_result=lambda r:
                      res.__setitem__(r.row_id, (r.finish_reason,
                                      list(r.token_ids), r.cumulative_logprob)))

    b = batcher(runner)
    later, polls, j0 = [ctx("plain", plain)], [], joined()

    def poll_new():
        polls.append(1)
        return later.pop(0) if len(polls) > 2 and later else None

    b.run_multi([ctx("fsm", [fsm])], on_job_done=lambda c, o: None,
                poll_new=poll_new)
    assert tuple(joined() - j0) == (0, 3)
    assert {i: res[i] for i in want} == want and len(res[0][1]) == 20


# -- one program, whatever the wave -------------------------------------------

def test_the_merge_of_unread_first_tokens_compiles_once():
    runner = runner_of("dense")
    tok = tok_of(runner)
    merge, sample = type(runner)._merge_first_jit, _admit_sample_jit
    b = batcher(runner)
    run(b, requests(tok, TEXTS[:2]))  # the warm wave
    m0, s0, j0 = merge._cache_size(), sample._cache_size(), joined()
    assert m0 >= 1
    for n in (1, 3, 7):
        run(b, requests(tok, [TEXTS[0]] * n))
    # and with windows in flight: the window before's sample row in
    # place of the host's own
    assert tuple(joined() - j0) == (1 + 3 + 7, 0)
    run(b, turnover(tok, 2 * B, temperature=0.0))
    assert (joined() - j0)[0] > 1 + 3 + 7 + B
    assert merge._cache_size() == m0 and sample._cache_size() == s0


@pytest.mark.parametrize("traffic,preset", [
    ("generate-long-output-jobs", "dense"),
    ("generate-block-diffusion-jobs", "block"),
])
def test_the_warm_groups_reach_the_unread_path(traffic, preset):
    """The warm-up of a cell is its traffic file's groups, one small job
    after another, each admitted as one wave into an empty machine
    (``perfbench/generators/batch_jobs.py`` ``warm``). As the files
    stand that is enough: every group of plain rows enters its window
    unread, which compiles the one program the path adds, and rows that
    turn over with windows in flight compile nothing after it. (Prompt
    lengths a twentieth of the file's: the shapes here are tiny.)"""
    doc = json.loads((Path(__file__).parent.parent / "perfbench" / "traffic"
                      / f"{traffic}.json").read_text())
    warm = doc["warm"]
    assert doc["output_schema"] is None and doc["sampling"]["temperature"] > 0
    runner = runner_of(preset, 16)
    tok = tok_of(runner)
    merge = type(runner)._merge_first_jit
    b = batcher(runner)
    free0, j0, m00 = b.free_page_count, joined(), merge._cache_size()
    rows = 0
    for g in warm["groups"][:9]:  # 1, 1, 1, 1, 2, 4, 8, 16, 16 rows
        texts = ["x" * max(int(g["chars"]) // 20, 1)] * int(g["rows"])
        run(b, requests(tok, texts, max_new_tokens=int(warm["max_new_tokens"]),
                        temperature=doc["sampling"]["temperature"]))
        rows += len(texts)
    assert tuple(joined() - j0) == (rows, 0)
    m0, s0 = merge._cache_size(), _admit_sample_jit._cache_size()
    # (a block model's rows have no first token to take from anywhere)
    assert m0 - m00 == (0 if preset == "block" else 1)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiled.append(kw.get("fun_name"))
        if event.endswith("backend_compile_duration") else None
    )
    lengths = [int(g["chars"]) // 20 for g in warm["groups"][:4]]
    texts = ["x" * max(lengths[i % 4], 1) for i in range(32)]
    out, _, _ = run(b, [
        dataclasses.replace(r, max_new_tokens=5 + 4 * (i % 3))
        for i, r in enumerate(requests(tok, texts, temperature=0.7))
    ])
    assert len(out) == 32 and (joined() - j0)[0] > rows + 16
    assert merge._cache_size() == m0
    assert _admit_sample_jit._cache_size() == s0
    touched = ("_merge_first_jit", "_admit_sample_jit", "_decode_block")
    assert not [c for c in compiled if c and any(t in c for t in touched)]
    all_free(b, runner, free0)


# -- the lookahead holds while rows end, and wastes no window ------------------

def test_a_row_at_its_end_does_not_hold_back_the_lookahead():
    """A row's reservation covers every token it may commit, so what a
    window writes past its pages lies past its cap, on the garbage page:
    a row in its last window or two no longer keeps the NEXT window of
    the whole batch from going out. Same tokens as at a depth of one."""
    runner = runner_of("dense", 4)
    tok = tok_of(runner)
    reqs = lambda: [  # noqa: E731
        dataclasses.replace(r, max_new_tokens=n) for r, n in zip(
            requests(tok, TEXTS[:4] + TEXTS[5:7], temperature=0.0),
            [6, 19, 10, 23, 7, 14],
        )
    ]
    b = batcher(runner)
    seen, dispatch = [], b._dispatch_pipelined

    def spy(pipe, batch, proj, K):
        PS = b.ecfg.kv_page_size
        short = [
            i for i in batch.active
            if len(b.slots[i].pages) * PS - b.slots[i].pos - int(proj[i]) < K
        ]
        seen.append((len(pipe), len(short)))
        return dispatch(pipe, batch, proj, K)

    b._dispatch_pipelined = spy
    free0 = b.free_page_count
    deep, _, _ = run(b, reqs())
    all_free(b, runner, free0)
    # windows went out behind one in flight while a row had no K
    # positions of its own left (the rule until PR 58 refused those)
    assert any(depth == 1 and short for depth, short in seen)
    one = batcher(runner)
    one.ecfg = dataclasses.replace(one.ecfg, decode_lookahead=1)
    flat, _, _ = run(one, reqs())
    # (the log-probabilities are summed a window at a time: compared to
    # the sum's own rounding)
    assert set(deep) == set(flat) == set(range(6))
    for i, (reason, tokens, logp) in deep.items():
        assert (reason, tokens) == flat[i][:2]
        assert logp == pytest.approx(flat[i][2], rel=1e-6)


@pytest.mark.parametrize("preset", ["state-slot", "routed"])
def test_a_row_past_its_pages_leaves_the_garbage_pages_state_alone(preset):
    """A window that runs past a row's reserved pages commits through
    page 0. Where a model's state is kept a slot a row, that must not
    point page 0 at the row's slot: an empty batch slot and every other
    row past its pages find their state through page 0, and would
    advance the slot, or the row it goes to next (only the padding of
    the next admission's bind had been putting it back). Rows that end
    in staggered windows over fewer slots, the last waves leaving slots
    empty: page 0 leads to the garbage slot after every dispatch, and
    the tokens of a lookahead of two are those of a depth of one."""
    runner = runner_of(preset)
    tok = tok_of(runner)
    lengths = [7, 15, 10, 23, 6, 14, 9, 18, 11, 5, 21, 8, 16, 12, 19, 13,
               22, 17, 6, 20, 10, 14, 24, 9, 12, 7]
    reqs = lambda: [  # noqa: E731
        dataclasses.replace(r, max_new_tokens=n) for r, n in zip(
            requests(tok, [TEXTS[i % 4] for i in range(len(lengths))],
                     temperature=0.0),
            lengths,
        )
    ]
    b = batcher(runner)
    past, of_page_0, dispatch = [], [], b._dispatch_pipelined

    def spy(pipe, batch, proj, K):
        room = b.ecfg.kv_page_size
        past.append(any(
            len(b.slots[i].pages) * room - b.slots[i].pos - int(proj[i]) < K
            for i in batch.active
        ))
        dispatch(pipe, batch, proj, K)
        if runner.cache.state_slot is not None:
            of_page_0.append(int(runner.cache.state_slot[0]))

    b._dispatch_pipelined = spy
    free0 = b.free_page_count
    deep, _, _ = run(b, reqs())
    all_free(b, runner, free0)
    assert sum(past) > 3 and not any(of_page_0)
    one = batcher(runner)
    one.ecfg = dataclasses.replace(one.ecfg, decode_lookahead=1)
    flat, _, _ = run(one, reqs())
    assert set(deep) == set(flat) == set(range(len(lengths)))
    for i, (reason, tokens, logp) in deep.items():
        assert (reason, tokens) == flat[i][:2], i
        assert logp == pytest.approx(flat[i][2], rel=1e-5)


def test_no_window_goes_out_that_no_row_can_use():
    """Rows of 1 + K tokens (a warm-up job): the window in flight ends
    every one of them, so the second of the lookahead is not
    dispatched."""
    runner = runner_of("dense")
    tok = tok_of(runner)
    K = runner.ecfg.decode_multi_step
    b = batcher(runner)
    calls, real = [], runner.decode_multi_async

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    runner.decode_multi_async = counted
    a0 = ahead()
    try:
        out, _, _ = run(b, requests(tok, TEXTS[:4], max_new_tokens=1 + K))
    finally:
        del runner.decode_multi_async
    assert all(len(r[1]) == 1 + K for r in out.values())
    assert len(calls) == 1 and run.ctx.stats.get("lost_stale", 0) == 0
    assert tuple(ahead() - a0) == (0, 1, 0)


def test_the_sample_splits_the_session_key_as_split_alone_does():
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    key = jax.random.PRNGKey(3)
    args = (np.full((4,), 0.8, np.float32), np.full((4,), 0.9, np.float32),
            np.zeros((4,), np.int32), None, None)
    after, sub = jax.random.split(key)
    t0, lp0, _, same = _admit_sample_jit(logits, sub, *args)
    t1, lp1, _, new = _admit_sample_jit(logits, key, *args, split=True)
    assert same is None
    assert np.array_equal(np.asarray(t0), np.asarray(t1))
    assert np.array_equal(np.asarray(lp0), np.asarray(lp1))
    assert np.array_equal(jax.random.key_data(new), jax.random.key_data(after))


def test_rows_a_window_long_keep_the_depth_at_one_while_rows_wait():
    """Eight rows of 1 + K tokens over four slots: every window in
    flight ends all its rows, and rows wait for their slots, so no
    window goes out ahead (its slots would be dead weight and the
    waiting rows a window late): two full windows, nothing lost."""
    runner = runner_of("dense", 4)
    tok = tok_of(runner)
    K = runner.ecfg.decode_multi_step
    b = batcher(runner)
    calls, real = [], runner.decode_multi_async

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    runner.decode_multi_async = counted
    try:
        out, _, _ = run(b, requests(tok, TEXTS[:4] + TEXTS[5:], max_new_tokens=1 + K,
                                    temperature=0.0))
    finally:
        del runner.decode_multi_async
    assert len(out) == 7 and all(len(r[1]) == 1 + K for r in out.values())
    st = run.ctx.stats
    assert len(calls) == 2 and st.get("lost_stale", 0) == 0
    assert st["row_steps"] == 7 * K


def test_half_a_batch_ending_while_rows_wait_holds_the_window_back():
    """Two long rows and a queue of rows a window long over four slots:
    while rows wait, half of the batch ends in every window in flight,
    over ``_AHEAD_ENDING``, so the window ahead is held back
    (``held_ending``) and each short row takes its slot in the next
    window; once the queue is empty the long rows' windows go out ahead
    (``sent``). Same tokens as at a depth of one."""
    runner = runner_of("dense", 4)
    tok = tok_of(runner)
    K = runner.ecfg.decode_multi_step
    lengths = [6 * K, 6 * K] + [1 + K] * 12
    reqs = lambda: [  # noqa: E731
        dataclasses.replace(r, max_new_tokens=n) for r, n in zip(
            requests(tok, [TEXTS[i % 4] for i in range(len(lengths))],
                     temperature=0.0),
            lengths,
        )
    ]
    b = batcher(runner)
    a0 = ahead()
    deep, _, _ = run(b, reqs())
    sent, unused, ending = ahead() - a0
    assert ending >= 2 and sent >= 1
    one = batcher(runner)
    one.ecfg = dataclasses.replace(one.ecfg, decode_lookahead=1)
    flat, _, _ = run(one, reqs())
    assert set(deep) == set(flat) == set(range(len(lengths)))
    for i, (reason, tokens, logp) in deep.items():
        assert (reason, tokens) == flat[i][:2]
        assert logp == pytest.approx(flat[i][2], rel=1e-5)


def test_a_second_job_of_the_same_shapes_lowers_nothing_again():
    """On one device nothing is committed: the pool, the weights, every
    result. An argument committed on its way into a window (the session
    key, the merged last tokens) would commit the pool that window
    returns, and every program that takes the pool would be lowered a
    second time at the next job: seconds of a cell's set-up. A fresh
    runner, two jobs of one shape: the second compiles nothing."""
    mcfg = MODEL_CONFIGS[PRESETS["state-slot"]]
    runner = ModelRunner(mcfg, _ecfg(decode_batch_size=4), num_pages=1 + 4 * MP)
    tok = tok_of(runner)
    b = batcher(runner)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiled.append(kw.get("fun_name"))
        if event.endswith("backend_compile_duration") else None
    )
    j0 = joined()
    run(b, requests(tok, TEXTS[:1], temperature=0.7))
    first = len(compiled)
    run(b, requests(tok, TEXTS[:1], temperature=0.7))
    assert first > 0 and compiled[first:] == []
    assert tuple(joined() - j0) == (2, 0)
    assert not runner.cache.state_slot.committed
