"""The admission wave (engine/scheduler.py ``_admit_batch`` /
``_resolve_wave``): a row's prefill and its first-token sample are
dispatched with nothing waited for, and the first tokens of everything
an iteration admitted come back in ONE host sync. A job that streams its
tokens cuts the wave at its own row, which is how these tests resolve a
wave row by row: every output of a wave must be bit-equal to that, on a
dense, a routed, a state-slot and a window-pool model, and whatever
releases or moves a slot must find every row armed."""

import dataclasses
import functools
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
}
TEXTS = [
    "hello", "a second row", "third", "the fourth of eight rows",
    # past prefill_chunk: it is admitted by _prefill_tick, a chunk an
    # iteration, and its last chunk joins that iteration's wave
    "row five is longer than one prefill chunk of thirty-two tokens",
    "six", "seven, nearly there", "eight",
]
WAVES = "sutro_admit_waves_total"
WAVE_ROWS = "sutro_admit_wave_rows_total"


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
        assert r.window_pool is not None
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


def counter(name: str) -> float:
    series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
    return sum(series.values())


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
    if getattr(runner, "state_slots", None) is not None:
        assert runner.state_slots.in_use == 0


def prefill_spans():
    return [
        s for s in telemetry.RECORDER.snapshot() if s["name"] == "prefill"
    ]


# -- a wave gives what row by row gives ---------------------------------------

@pytest.mark.parametrize("seeded", [False, True], ids=["unseeded", "seeded"])
@pytest.mark.parametrize("pbs", [1, 8], ids=["batch1", "batch8"])
@pytest.mark.parametrize("preset", list(PRESETS))
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
    t, lp = _admit_sample_jit(
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
    # resolved, then emitted before any decode dispatch was made
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
    for name in (WAVES, WAVE_ROWS):
        assert f"| `{name}` | counter |" in doc
    assert "`wave_rows`" in doc


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
        assert runner.state_slots.in_use == k - 1
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
