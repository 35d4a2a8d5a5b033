"""Hydragen-style split decode over the shared prefix
(EngineConfig.prefix_split + ops/pallas_paged.prefix_attention_carry +
paged-kernel carry injection).

Op-level parity lives in tests/test_pallas_kernels.py
(test_paged_decode_prefix_carry_injection). Here the FULL engine path
runs with the real Pallas kernels in interpret mode on CPU: prefix
cache detection -> split operands (_split_pfx) -> carry injection in
every decode dispatch — outputs must match the same engine with the
split disabled, and the carry helper must actually have been used."""

import functools

import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.models.configs import MODEL_CONFIGS

PREFIX = "system: classify the review. review: "  # 37 chars -> 4 pages
SUFFIXES = ["good stuff", "bad stuff", "meh", "ok product arrived"]


def _force_interpret(monkeypatch):
    """Run the engine's Pallas path on CPU: kernels in interpret mode,
    shape gates opened (tiny test heads fail the TPU-lane gates)."""
    from sutro_tpu.ops import pallas_gmm, pallas_kv, pallas_paged, pallas_ssm

    monkeypatch.setattr(
        pallas_gmm, "grouped_matmul",
        functools.partial(pallas_gmm.grouped_matmul, interpret=True),
    )
    monkeypatch.setattr(
        pallas_ssm, "ssm_state_read",
        functools.partial(pallas_ssm.ssm_state_read, interpret=True),
    )
    monkeypatch.setattr(
        pallas_paged, "paged_decode_supported", lambda *a: True
    )
    monkeypatch.setattr(
        pallas_paged,
        "paged_decode_attention",
        functools.partial(
            pallas_paged.paged_decode_attention, interpret=True
        ),
    )
    monkeypatch.setattr(
        pallas_kv,
        "kv_write_pallas",
        functools.partial(pallas_kv.kv_write_pallas, interpret=True),
    )
    from sutro_tpu.ops import pallas_flash

    monkeypatch.setattr(
        pallas_flash, "flash_prefill_supported", lambda *a, **k: False
    )


def _run(tok, split: bool, monkeypatch):
    _force_interpret(monkeypatch)
    ecfg = EngineConfig(
        kv_page_size=8,
        max_pages_per_seq=10,
        max_model_len=80,
        decode_batch_size=4,
        use_pallas=True,
        param_dtype="float32",
        activation_dtype="float32",
        decode_multi_step=1,
        decode_lookahead=1,
        prefix_split=split,
    )
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
        stop_ids=tok.stop_ids(),
    )
    res = {}
    out = b.run(
        [
            GenRequest(
                row_id=i,
                prompt_ids=np.array(tok.encode(PREFIX + s), np.int32),
                max_new_tokens=5,
                temperature=0.0,
            )
            for i, s in enumerate(SUFFIXES)
        ],
        on_result=lambda r: res.__setitem__(r.row_id, r),
    )
    assert out == "completed"
    # the job's shared prefix must have been detected (split operands
    # exist only when ctx.prefix does)
    naive = sum(len(tok.encode(PREFIX + s)) for s in SUFFIXES)
    assert b.prefill_tokens < naive
    return {i: r.token_ids for i, r in res.items()}


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
def test_two_prefix_groups_cobatched(byte_tok, monkeypatch):
    """Two templated jobs with DIFFERENT shared prefixes co-batched:
    each gets its own carry group (disjoint member sets combine by
    max/sum/sum), and outputs stay identical to the unsplit kernel."""
    from sutro_tpu.engine.scheduler import JobCtx

    _force_interpret(monkeypatch)
    tok = byte_tok
    PFX2 = "system: extract the named entity. text: "

    def run(split):
        ecfg = EngineConfig(
            kv_page_size=8,
            max_pages_per_seq=10,
            max_model_len=80,
            decode_batch_size=4,
            use_pallas=True,
            param_dtype="float32",
            activation_dtype="float32",
            decode_multi_step=1,
            decode_lookahead=1,
            prefix_split=split,
        )
        b = ContinuousBatcher(
            ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
            stop_ids=tok.stop_ids(),
        )

        def reqs(texts, base):
            return [
                GenRequest(
                    row_id=base + i,
                    prompt_ids=np.array(tok.encode(t), np.int32),
                    max_new_tokens=4,
                    temperature=0.0,
                )
                for i, t in enumerate(texts)
            ]

        ga, gb = {}, {}
        st = b.run_multi(
            [
                JobCtx(
                    job_id="A",
                    pending=reqs([PREFIX + s for s in SUFFIXES[:2]], 0),
                    on_result=lambda r: ga.__setitem__(r.row_id, r),
                    priority=1,
                    seq=0,
                ),
                JobCtx(
                    job_id="B",
                    pending=reqs([PFX2 + s for s in ("alpha", "beta")], 100),
                    on_result=lambda r: gb.__setitem__(r.row_id, r),
                    priority=1,
                    seq=1,
                ),
            ],
            on_job_done=lambda c, o: None,
        )
        assert st == "completed"
        return (
            {i: r.token_ids for i, r in ga.items()},
            {i: r.token_ids for i, r in gb.items()},
        )

    on_a, on_b = run(True)
    off_a, off_b = run(False)
    assert on_a == off_a and on_b == off_b


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
def test_engine_split_decode_matches_unsplit(byte_tok, monkeypatch):
    from sutro_tpu.ops import pallas_paged

    calls = []
    real = pallas_paged.prefix_attention_carry

    def record(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(
        pallas_paged, "prefix_attention_carry", record
    )
    on = _run(byte_tok, True, monkeypatch)
    assert calls, "split decode never used the carry helper"
    n_split = len(calls)
    calls.clear()
    off = _run(byte_tok, False, monkeypatch)
    assert not calls, "carry helper ran with prefix_split disabled"
    assert on == off, "split decode changed greedy outputs"
    # the carry is traced once per jit compilation (it sits inside the
    # layer lax.scan, and later dispatches reuse the compiled program),
    # so call COUNT is compilation count — n_split >= 1 is the signal
    assert n_split >= 1


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
def test_engine_split_decode_in_place_kernel(byte_tok, monkeypatch):
    """Same engine path, but with the IN-PLACE prefix-carry kernel
    (page-indexed BlockSpecs over the pool) forced on: the shape gate
    is opened for the tiny test heads and the kernel runs in interpret
    mode — outputs must still match the unsplit engine and the pallas
    carry (not the XLA gather) must have been dispatched."""
    from sutro_tpu.ops import pallas_paged

    calls = []
    real = pallas_paged.prefix_attention_carry_pallas

    def record(*a, **kw):
        calls.append(1)
        kw["interpret"] = True
        return real(*a, **kw)

    monkeypatch.setattr(
        pallas_paged, "prefix_carry_supported", lambda *a, **k: True
    )
    monkeypatch.setattr(
        pallas_paged, "prefix_attention_carry_pallas", record
    )
    on = _run(byte_tok, True, monkeypatch)
    assert calls, "split decode never used the in-place carry kernel"
    calls.clear()
    off = _run(byte_tok, False, monkeypatch)
    assert not calls, "carry kernel ran with prefix_split disabled"
    assert on == off, "in-place split decode changed greedy outputs"
