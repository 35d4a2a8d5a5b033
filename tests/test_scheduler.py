"""Continuous-batching scheduler behaviors: ordering, cancellation,
truncation, admission, determinism, sampling-param plumbing."""

import numpy as np

from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest

from .conftest import make_requests


def run_all(batcher, reqs, **kw):
    res = {}
    batcher.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r), **kw)
    return res


def test_all_rows_complete_in_order_keyed(tiny_runner, byte_tok):
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    reqs = make_requests(
        byte_tok,
        [f"row number {i}" for i in range(9)],
        max_new_tokens=6,
        temperature=0.5,
    )
    res = run_all(b, reqs)
    assert set(res) == set(range(9))
    assert all(r.input_tokens > 0 for r in res.values())


def test_greedy_determinism_across_batching(tiny_runner, byte_tok):
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    reqs = make_requests(
        byte_tok, ["same prompt"] * 4, max_new_tokens=8, temperature=0.0
    )
    res = run_all(b, reqs)
    seqs = [tuple(res[i].token_ids) for i in range(4)]
    assert len(set(seqs)) == 1


def test_truncation_and_too_long(tiny_runner, byte_tok):
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    long_ids = np.arange(500, dtype=np.int32) % 200
    reqs = [
        GenRequest(row_id=0, prompt_ids=long_ids, max_new_tokens=4),
        GenRequest(
            row_id=1, prompt_ids=long_ids, max_new_tokens=4,
            allow_truncate=False,
        ),
    ]
    res = run_all(b, reqs)
    assert res[0].finish_reason in ("length", "stop")
    assert res[1].finish_reason == "error_too_long"
    assert res[1].token_ids == []


def test_cancellation(tiny_runner, byte_tok):
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    calls = [0]

    def cancel():
        calls[0] += 1
        return calls[0] > 2

    res = run_all(
        b,
        make_requests(byte_tok, ["a", "b"], max_new_tokens=50),
        should_cancel=cancel,
    )
    assert all(r.finish_reason == "cancelled" for r in res.values())


def test_progress_stream_fields(tiny_runner, byte_tok):
    """Progress updates carry the reference NDJSON token fields
    (sdk.py:339-366)."""
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    updates = []
    run_all(
        b,
        make_requests(byte_tok, ["x", "y"], max_new_tokens=4),
        on_progress=updates.append,
        progress_every=0.0,
    )
    assert updates, "no progress reported"
    last = updates[-1]
    assert {
        "rows_completed",
        "input_tokens",
        "output_tokens",
        "total_tokens_processed_per_second",
    } <= set(last)
    assert last["rows_completed"] == 2


def test_pages_released(tiny_runner, byte_tok):
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    free0 = b.free_page_count
    run_all(b, make_requests(byte_tok, ["p1", "p2", "p3"], max_new_tokens=5))
    assert b.free_page_count == free0


def test_constraint_mask_smaller_than_model_vocab(tiny_ecfg, byte_tok):
    """Tokenizer vocab < padded model vocab: masks must pad with False
    (code-review regression — real HF checkpoints pad the embedding)."""
    import numpy as np

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["tiny-dense"]
    short = cfg.vocab_size - 100  # pretend tokenizer is 100 ids short

    class HeadOnly:
        """Allows only token id 7, mask sized to the short vocab."""

        def allowed_tokens(self):
            m = np.zeros((short,), bool)
            m[7] = True
            return m

        def advance(self, tok):
            pass

        def is_complete(self):
            return False

    b = ContinuousBatcher(
        ModelRunner(cfg, tiny_ecfg), stop_ids=byte_tok.stop_ids()
    )
    res = {}
    b.run(
        [
            GenRequest(
                row_id=0,
                prompt_ids=np.array(byte_tok.encode("x"), np.int32),
                max_new_tokens=4,
                constraint=HeadOnly(),
            )
        ],
        on_result=lambda r: res.__setitem__(r.row_id, r),
    )
    assert all(t == 7 for t in res[0].token_ids)


def test_job_perf_profile_recorded(tiny_ecfg, byte_tok, tmp_path, monkeypatch):
    """Completed jobs carry a StepTimer latency summary in their record
    (engine/profiling.py; SURVEY §5.1 engine-level profiling)."""
    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    from sutro_tpu.engine.api import LocalEngine

    eng = LocalEngine(tiny_ecfg)
    job_id = eng.submit_batch_inference(
        {"model": "tiny-dense", "inputs": ["a", "bb"],
         "sampling_params": {"max_new_tokens": 5}}
    )
    import time

    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        from sutro_tpu.interfaces import JobStatus

        if JobStatus(eng.job_status(job_id)).is_terminal():
            break
        time.sleep(0.2)
    rec = eng.get_job(job_id)
    assert rec["status"] == "SUCCEEDED", rec.get("failure_reason")
    perf = rec["perf"]
    assert perf and "decode" in perf and "prefill" in perf
    # both rows ride ONE batched prefill dispatch (runner.prefill_batch),
    # and the admission wave's one resolving block fetches both first
    # tokens (a dispatch a row would read 3)
    assert perf["prefill"]["count"] == 2
    assert perf["decode"]["p50_ms"] > 0


def test_multi_step_matches_single_step_greedy(tiny_ecfg, byte_tok):
    """Fused multi-step decode windows (decode_multi_step) must produce
    exactly the single-step greedy outputs (greedy is rng-independent)."""
    import dataclasses

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    texts = ["alpha", "beta gamma", "", "longer prompt here"]

    def run(multi):
        ecfg = dataclasses.replace(tiny_ecfg, decode_multi_step=multi)
        b = ContinuousBatcher(
            ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
            stop_ids=byte_tok.stop_ids(),
        )
        res = run_all(
            b,
            make_requests(byte_tok, texts, max_new_tokens=11,
                          temperature=0.0),
        )
        return {i: (tuple(r.token_ids), r.finish_reason)
                for i, r in res.items()}

    assert run(1) == run(8)


def test_batched_prefill_matches_single(tiny_ecfg, byte_tok):
    """Greedy outputs must be identical whether rows prefill one per
    dispatch (prefill_batch_size=1) or batched — batching is purely an
    execution-shape change."""
    import dataclasses

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    texts = ["alpha beta", "gamma", "delta epsilon zeta", "eta", "theta!"]
    outs = []
    for pbs in (1, 4):
        ecfg = dataclasses.replace(tiny_ecfg, prefill_batch_size=pbs)
        runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
        b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
        reqs = make_requests(
            byte_tok, texts, max_new_tokens=6, temperature=0.0
        )
        res = run_all(b, reqs)
        outs.append([tuple(res[i].token_ids) for i in range(len(texts))])
    assert outs[0] == outs[1]


def test_inadmissible_row_fails_alone(tiny_ecfg, byte_tok):
    """A row whose prompt+max_new exceeds total KV capacity fails with a
    per-row error result; every other row still succeeds and the job
    completes (no whole-job MemoryError)."""
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    # cache holds only 7 usable pages (56 tokens) < the 16 pages the bad
    # row's worst case needs — it can never fit even an empty machine
    runner = ModelRunner(
        MODEL_CONFIGS["tiny-dense"], tiny_ecfg, num_pages=8
    )
    b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
    ok1 = make_requests(byte_tok, ["good row"], max_new_tokens=4)[0]
    bad = GenRequest(
        row_id=1,
        prompt_ids=(np.arange(40) % 200).astype(np.int32),
        max_new_tokens=tiny_ecfg.max_context(),
    )
    ok2 = make_requests(byte_tok, ["another good row"], max_new_tokens=4)[0]
    ok2 = GenRequest(
        row_id=2, prompt_ids=ok2.prompt_ids, max_new_tokens=4
    )
    res = run_all(b, [ok1, bad, ok2])
    assert set(res) == {0, 1, 2}
    assert res[1].finish_reason == "error_capacity"
    assert res[1].token_ids == []
    assert res[0].finish_reason in ("stop", "length")
    assert res[2].finish_reason in ("stop", "length")


def test_python_fallback_batched_admission(tiny_runner, byte_tok, monkeypatch):
    """The pure-Python allocator path (no native runtime) must admit a
    multi-row batch into DISTINCT slots — regression for a reservation
    collision where every same-batch row got slots.index(None)."""
    import sutro_tpu.engine.native_runtime as nr

    monkeypatch.setattr(nr, "maybe_native_runtime", lambda *a, **k: None)
    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    assert b.native is None and b.allocator is not None
    texts = ["one", "two", "three", "four"]
    res = run_all(
        b, make_requests(byte_tok, texts, max_new_tokens=5)
    )
    assert set(res) == set(range(len(texts)))
    assert b.free_page_count == b.allocator.num_pages - 1  # all released


def test_page_allocator_contiguous_runs():
    """Contiguous-first allocation: runs are ascending and re-allocation
    after frees still finds holes (first-fit), falling back to scattered
    only when no hole fits."""
    from sutro_tpu.engine.kvcache import PageAllocator

    a = PageAllocator(num_pages=17)  # pages 1..16
    r1 = a.alloc(4)
    r2 = a.alloc(4)
    r3 = a.alloc(4)
    for r in (r1, r2, r3):
        assert r == list(range(r[0], r[0] + 4))
    a.free(r2)  # hole of 4 in the middle
    r4 = a.alloc(3)  # fits the hole (first fit)
    assert r4 == list(range(r4[0], r4[0] + 3))
    a.free(r1)
    a.free(r3)
    a.free(r4)
    assert a.free_count == 16
    big = a.alloc(16)
    assert big == list(range(1, 17))


def test_truncation_reserves_schema_room(tiny_runner):
    """A long prompt on a constrained row is truncated far enough that
    the schema's minimal JSON still fits (regression: prompts that fill
    the context left 1 token of room and emitted just "{")."""
    import json

    from sutro_tpu.engine.constrain import schema_constraint_factory
    from sutro_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {
            "scratchpad": {"type": "string"},
            "label": {"enum": ["a", "b"]},
        },
        "required": ["scratchpad", "label"],
    }
    fac = schema_constraint_factory(schema, tok)
    b = ContinuousBatcher(tiny_runner, stop_ids=tok.stop_ids())
    cap = tiny_runner.ecfg.max_context()
    long_prompt = np.asarray(
        tok.encode("x" * (cap + 40)), np.int32
    )
    results = {}
    b.run(
        [
            GenRequest(
                row_id=0, prompt_ids=long_prompt, max_new_tokens=64,
                temperature=0.0, constraint=fac(),
            )
        ],
        on_result=lambda r: results.__setitem__(r.row_id, r),
    )
    r = results[0]
    assert r.finish_reason not in ("error_too_long",)
    obj = json.loads(tok.decode(r.token_ids))
    assert obj["label"] in ("a", "b")


def test_unfittable_schema_fails_row_clearly(tiny_runner):
    """If the schema's minimal JSON cannot fit the context at all, the
    row fails with error_too_long instead of emitting invalid JSON."""
    from sutro_tpu.engine.constrain import schema_constraint_factory
    from sutro_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cap = tiny_runner.ecfg.max_context()
    # enum of one long literal whose minimal JSON exceeds the context
    schema = {
        "type": "object",
        "properties": {"v": {"enum": ["y" * (cap + 16)]}},
        "required": ["v"],
    }
    fac = schema_constraint_factory(schema, tok)
    b = ContinuousBatcher(tiny_runner, stop_ids=tok.stop_ids())
    results = {}
    b.run(
        [
            GenRequest(
                row_id=0,
                prompt_ids=np.asarray(tok.encode("hi"), np.int32),
                max_new_tokens=cap + 64, temperature=0.0,
                constraint=fac(),
            )
        ],
        on_result=lambda r: results.__setitem__(r.row_id, r),
    )
    assert results[0].finish_reason == "error_too_long"


def test_stop_sequences_end_generation(tiny_runner, byte_tok):
    """A stop sequence appearing in the decoded output (even spanning
    token boundaries) finishes the row with reason "stop"."""
    b = ContinuousBatcher(
        tiny_runner, stop_ids=byte_tok.stop_ids(),
        token_bytes=byte_tok.token_bytes,
    )
    results = {}
    # force the output deterministically by constraining to a const
    # string that CONTAINS the stop sequence
    from sutro_tpu.engine.constrain import schema_constraint_factory

    fac = schema_constraint_factory(
        {"const": "abcSTOPdef"}, byte_tok
    )
    b.run(
        [
            GenRequest(
                row_id=0,
                prompt_ids=np.asarray(byte_tok.encode("x"), np.int32),
                max_new_tokens=40, temperature=0.0, constraint=fac(),
                stop_seqs=[b"STOP"],
            )
        ],
        on_result=lambda r: results.__setitem__(r.row_id, r),
    )
    r = results[0]
    assert r.finish_reason == "stop"
    out = byte_tok.decode(r.token_ids)
    assert "STOP" in out            # engine stops AT the sequence...
    assert not out.endswith("def")  # ...without generating the rest


def test_repetition_penalty_via_scheduler(tiny_runner, byte_tok):
    """Penalty rows route through the single-step path with host-side
    counts; a strong repetition penalty measurably reduces repeats vs
    the unpenalized greedy decode of the same prompt."""
    def run(rep):
        b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
        results = {}
        b.run(
            [
                GenRequest(
                    row_id=0,
                    prompt_ids=np.asarray(
                        byte_tok.encode("abab"), np.int32
                    ),
                    max_new_tokens=24, temperature=0.0,
                    repetition_penalty=rep,
                )
            ],
            on_result=lambda r: results.__setitem__(r.row_id, r),
        )
        return results[0].token_ids

    base = run(1.0)
    pen = run(8.0)

    def max_run(ids):
        best = cur = 1
        for a, c in zip(ids, ids[1:]):
            cur = cur + 1 if a == c else 1
            best = max(best, cur)
        return best

    # greedy tiny models loop hard; a strong penalty must break the
    # longest repeat run (or change the output entirely)
    assert pen != base
    if len(base) > 4:
        assert max_run(pen) <= max_run(base)


def test_speculative_rejection_is_per_row(tiny_runner, byte_tok, monkeypatch):
    """One adversarial constrained row (scaffold-heavy const schema,
    rejected nearly every window) must NOT degrade the batch to masked
    single-steps: the rejecting row takes its FSM-masked step inside the
    next window (allowed0) while other rows keep full window cadence."""
    import json

    from sutro_tpu.engine.constrain import schema_constraint_factory

    calls = {"window": 0, "window_masked": 0, "single": 0}
    orig_window = tiny_runner.decode_window
    orig_step = tiny_runner.decode_step

    def window(*a, **kw):
        calls["window"] += 1
        if kw.get("allowed0") is not None:
            calls["window_masked"] += 1
        return orig_window(*a, **kw)

    def step(*a, **kw):
        calls["single"] += 1
        return orig_step(*a, **kw)

    monkeypatch.setattr(tiny_runner, "decode_window", window)
    monkeypatch.setattr(tiny_runner, "decode_step", step)
    b = ContinuousBatcher(
        tiny_runner, stop_ids=byte_tok.stop_ids(),
        token_bytes=byte_tok.token_bytes,
    )
    # this test pins the WINDOW path's per-row rejection recovery; the
    # FSM fast-forward would otherwise commit the const row's forced
    # run without dispatching any window at all (its own invariant is
    # pinned by tests/test_fastforward.py)
    import dataclasses as _dc

    b.ecfg = _dc.replace(b.ecfg, constrain_fastforward=0)
    fac = schema_constraint_factory({"const": "zqxzqxzqxzqx"}, byte_tok)
    reqs = [
        GenRequest(
            row_id=0,
            prompt_ids=np.array(byte_tok.encode("adv"), np.int32),
            max_new_tokens=40, temperature=0.0, constraint=fac(),
        ),
    ] + [
        GenRequest(
            row_id=i,
            prompt_ids=np.array(byte_tok.encode(text), np.int32),
            # window-aligned cap: a non-multiple of decode_multi_step
            # would run its TAIL single-step by the documented
            # all-or-nothing window rule, which is not what this test
            # measures. Long enough to outlast the const row (a token a
            # window): once the plain rows are gone, a batch of refused
            # windows alone is worth less than masked steps and takes
            # them (_choose_path)
            max_new_tokens=14 * tiny_runner.ecfg.decode_multi_step,
            temperature=0.0,
        )
        for i, text in ((1, "bystander"), (2, "another one"))
    ]
    res = {}
    b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
    out0 = b"".join(byte_tok.token_bytes(t) for t in res[0].token_ids)
    assert json.loads(out0.decode()) == "zqxzqxzqxzqx"
    assert res[0].finish_reason == "schema_complete"
    for i in (1, 2):
        assert len(res[i].token_ids) == (
            14 * tiny_runner.ecfg.decode_multi_step
        )
    # the invariant under test: rejections recovered inside windows,
    # never by flipping the whole batch to masked single-steps
    assert calls["single"] == 0, calls
    assert calls["window_masked"] >= 1, calls
    assert calls["window"] >= 2, calls


def test_masked_window_step_trusts_mask_no_livelock(tiny_runner, byte_tok):
    """Budget-infeasible corner: allowed_tokens degrades to unfiltered
    while token_allowed still rejects. The flagged row's step-0 token is
    mask-chosen, so it must be accepted WITHOUT re-verification (the old
    masked single-step's semantics) — re-checking would reject it and
    spin the scheduler forever at zero progress."""

    class DivergentConstraint:
        def __init__(self, vocab):
            self.v = vocab

        def allowed_tokens(self, remaining=None):
            return np.ones(self.v, bool)  # degrade: unfiltered

        def token_allowed(self, tok, remaining=None):
            return False  # strict check: nothing fits

        def advance(self, tok):
            pass

        def is_complete(self):
            return False

        def min_tokens(self):
            return 1

    b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
    reqs = [
        GenRequest(
            row_id=0,
            prompt_ids=np.array(byte_tok.encode("x"), np.int32),
            max_new_tokens=6, temperature=0.0,
            constraint=DivergentConstraint(tiny_runner.mcfg.vocab_size),
        )
    ]
    res = {}
    b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
    # terminates (no livelock) and makes real progress via masked steps
    assert len(res[0].token_ids) == 6


def test_row_seed_independent_of_batch_composition(tiny_runner, byte_tok):
    """The reference's random_seed_per_input contract (sample()
    docstring): a seeded row's output stream is reproducible regardless
    of batch composition — pinned across admission-group sizes (1-row
    job vs 3-row job). The 3-row group pads to the 4-bucket in
    round-5's bucketed admission sampling, so this also pins that a
    padded group does not perturb real rows' draws."""
    import numpy as np

    from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest

    def run_job(reqs):
        b = ContinuousBatcher(tiny_runner, stop_ids=byte_tok.stop_ids())
        res = {}
        out = b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
        assert out == "completed"
        return res

    def mk(i, txt, seed=None):
        return GenRequest(
            row_id=i,
            prompt_ids=np.frombuffer(txt.encode(), np.uint8).astype(
                np.int32
            ),
            max_new_tokens=10,
            temperature=0.9,
            row_seed=seed,
        )

    solo = run_job([mk(0, "the quick brown fox", seed=42)])
    crowd = run_job(
        [
            mk(0, "alpha"),
            mk(1, "much longer prompt here padding things"),
            mk(2, "the quick brown fox", seed=42),
        ]
    )
    assert solo[0].token_ids == crowd[2].token_ids


class _AdmitStubRunner:
    """Minimal runner surface for admission-only scheduler tests."""

    def __init__(self, ecfg, vocab=300):
        class _M:
            vocab_size = vocab

        self.ecfg = ecfg
        self.mcfg = _M()
        self.sp = 1
        self.pp = 1
        self.num_pages = 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq


def _parity_ecfg():
    from sutro_tpu.engine.config import EngineConfig

    return EngineConfig(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
    )


def test_admission_parity_prefix_covers_whole_need(monkeypatch):
    """When the job's shared prefix already covers a row's worst-case
    page need (own < 1 before clamping), BOTH admission paths must
    clamp to 1 own page and admit while the table row has room —
    native rt_try_admit_pfx always did; the Python fallback used to
    reject (`own < 1 -> None`), diverging from the C++ verdict."""
    import pytest

    from sutro_tpu.engine import native_runtime as nr
    from sutro_tpu.engine.scheduler import JobCtx, _SharedPrefix

    verdicts = {}
    for native in (False, True):
        monkeypatch.setenv(
            "SUTRO_NATIVE_RUNTIME", "1" if native else "0"
        )
        nr._lib = None
        nr._lib_failed = False
        if native and not nr.is_available():
            nr._lib = None
            nr._lib_failed = False
            pytest.skip("native toolchain unavailable")
        try:
            ecfg = _parity_ecfg()
            b = ContinuousBatcher(_AdmitStubRunner(ecfg), stop_ids=[0])
            assert (b.native is not None) == native
            # a prefix of 4 pages (32 tokens) while the row's whole
            # worst case is 1 page: own = 1 - 4 < 1 before the clamp
            if native:
                pfx_pages = b.native.alloc_pages(4)
            else:
                pfx_pages = b.allocator.alloc(4)
            ctx = JobCtx(
                job_id="parity", pending=[], on_result=lambda r: None
            )
            ctx.prefix = _SharedPrefix(tokens=32, pages=list(pfx_pages))
            req = GenRequest(
                row_id=0,
                prompt_ids=np.arange(3, dtype=np.int32),
                max_new_tokens=2,
            )
            r = b._reserve(req, ctx)
            assert r is not None, f"native={native} rejected"
            slot_idx, own_pages, table = r
            # table head carries the prefix, exactly one own page after
            assert list(table[:4]) == list(pfx_pages)
            assert len(list(own_pages)) == 1
            assert table[4] == list(own_pages)[0]
            verdicts[native] = True
        finally:
            nr._lib = None
            nr._lib_failed = False
    assert verdicts.get(False) == verdicts.get(True)


def test_admission_parity_prefix_fills_table_row(monkeypatch):
    """Companion bound: when the prefix already fills the whole table
    row (npfx == MP), the clamped own page has nowhere to go — BOTH
    paths must reject (the native side grew this guard for a heap
    smash; the Python side must agree)."""
    import pytest

    from sutro_tpu.engine import native_runtime as nr
    from sutro_tpu.engine.scheduler import JobCtx, _SharedPrefix

    for native in (False, True):
        monkeypatch.setenv(
            "SUTRO_NATIVE_RUNTIME", "1" if native else "0"
        )
        nr._lib = None
        nr._lib_failed = False
        if native and not nr.is_available():
            nr._lib = None
            nr._lib_failed = False
            pytest.skip("native toolchain unavailable")
        try:
            ecfg = _parity_ecfg()
            b = ContinuousBatcher(_AdmitStubRunner(ecfg), stop_ids=[0])
            MP = ecfg.max_pages_per_seq
            if native:
                pfx_pages = b.native.alloc_pages(MP)
            else:
                pfx_pages = b.allocator.alloc(MP)
            ctx = JobCtx(
                job_id="parity2", pending=[], on_result=lambda r: None
            )
            ctx.prefix = _SharedPrefix(
                tokens=MP * ecfg.kv_page_size, pages=list(pfx_pages)
            )
            req = GenRequest(
                row_id=0,
                prompt_ids=np.arange(3, dtype=np.int32),
                max_new_tokens=2,
            )
            assert b._reserve(req, ctx) is None, f"native={native}"
        finally:
            nr._lib = None
            nr._lib_failed = False


def test_plain_window_zero_budget_finishes_immediately(byte_tok):
    """_accept_plain_window with a non-positive remaining budget must
    emit the row with ZERO tokens taken — the old max(..., 1) silently
    accepted one token past max_new_tokens / the context limit."""
    from sutro_tpu.engine import native_runtime as nr
    from sutro_tpu.engine.scheduler import _Slot

    ecfg = _parity_ecfg()
    import sutro_tpu.engine.scheduler as sched

    b = ContinuousBatcher.__new__(ContinuousBatcher)
    # hand-build just enough batcher state for the unit call
    b.ecfg = ecfg
    b.vocab = 300
    b.stop_ids = {0}
    b._stop_arr = np.array([0], np.int64)
    b._max_ctx = ecfg.max_context()
    b.native = None
    from sutro_tpu.engine.kvcache import PageAllocator

    b.allocator = PageAllocator(16)
    b.slots = [None] * 4
    b._gen = [0] * 4
    b._needs_mask = set()
    from sutro_tpu.engine.profiling import StepTimer

    b.timer = StepTimer()

    req = GenRequest(
        row_id=7, prompt_ids=np.arange(4, dtype=np.int32),
        max_new_tokens=3,
    )
    pages = b.allocator.alloc(2)
    slot = _Slot(req=req, pages=pages, pos=7, last_token=5)
    slot.out_ids = [5, 6, 9]  # already AT the max_new cap
    results = {}
    ctx = sched.JobCtx(
        job_id="zb", pending=[],
        on_result=lambda r: results.setdefault(r.row_id, r),
    )
    slot.job = ctx
    ctx.n_slots = 1
    b.slots[1] = slot
    wK = 4
    toks = np.full((wK, 4), 9, np.int32)
    logps = np.full((wK, 4), -1.0, np.float32)
    lost = {}
    b._accept_plain_window([1], toks, logps, wK, lost)
    # the whole window's steps committed nothing, for the row's job too
    assert lost == {"finished": wK} and ctx.stats["lost_finished"] == wK
    assert 7 in results, "row must finish"
    assert len(results[7].token_ids) == 3  # nothing accepted past cap
    assert results[7].finish_reason == "length"
    assert b.slots[1] is None
    assert b.allocator.free_count == 15  # PageAllocator(16): page 0 reserved
