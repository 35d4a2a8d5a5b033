"""Round-4 feature composition: shared-prefix KV caching + cross-job
co-batching + piggybacked chunked prefill + pipelined fused windows +
int8 KV cache in ONE engine session. Each feature is pinned exact in
isolation by its own test file; this asserts the COMPOSITION:

- fp leg: with full-precision KV, the composed co-batched session must
  produce outputs bit-identical to solo runs with prefix cache,
  pipelining (windows in flight: 1) and piggyback all DISABLED — the
  three features are exactness-preserving and must stay so when
  stacked.
- int8 leg: with kv_quantize="int8" the comparison baseline must share
  the same KV READ PATTERN (same config, solo): chunked/prefix prefill
  re-reads earlier K/V from quantized pages where a whole-prompt
  prefill attends over exact in-flight K/V, so cross-pattern token
  equality is not a contract under quantization — co-batching, however,
  must still be a pure scheduling change (exact vs same-config solo).

Plus invariants: no leaked pages (incl. the shared prefix's) and the
prefix cache actually saving prefill tokens in both legs.
"""

import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import (
    ContinuousBatcher,
    GenRequest,
    JobCtx,
)
from sutro_tpu.models.configs import MODEL_CONFIGS

PREFIX = (
    "system: classify the following review as positive or negative. "
    "review: "
)
A_SUFFIXES = [
    "great product works great",
    "terrible broke on day one",
    "great product came late but works",
    # long suffix: exceeds prefill_chunk=16 so its prefill rides the
    # chunked path, which the piggyback interleaves with live decode
    "the quality is ok but the packaging was damaged and the seller "
    "never answered my messages about a replacement unit",
    "love it love it love it",
    "not what the picture showed",
]
B_TEXTS = ["quick check a", "quick check b", "quick check c"]


def _ecfg(**kw):
    base = dict(
        kv_page_size=8,
        max_pages_per_seq=32,
        max_model_len=256,
        decode_batch_size=4,
        use_pallas=False,
        param_dtype="float32",
        activation_dtype="float32",
        decode_multi_step=4,
        decode_lookahead=2,
        prefill_chunk=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def _reqs(tok, texts):
    return [
        GenRequest(
            row_id=i,
            prompt_ids=np.array(tok.encode(t), np.int32),
            max_new_tokens=10,
            temperature=0.0,
        )
        for i, t in enumerate(texts)
    ]


def _solo(ecfg, tok, texts):
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
        stop_ids=tok.stop_ids(),
    )
    res = {}
    assert (
        b.run(
            _reqs(tok, texts),
            on_result=lambda r: res.__setitem__(r.row_id, r),
        )
        == "completed"
    )
    return {i: r.token_ids for i, r in res.items()}


def _cobatch(ecfg, tok):
    a_texts = [PREFIX + s for s in A_SUFFIXES]
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
        stop_ids=tok.stop_ids(),
    )
    free0 = b.free_page_count
    got_a, got_b, done = {}, {}, []
    state = b.run_multi(
        [
            JobCtx(
                job_id="A",
                pending=_reqs(tok, a_texts),
                on_result=lambda r: got_a.__setitem__(r.row_id, r),
                priority=1,
                seq=0,
            ),
            JobCtx(
                job_id="B",
                pending=_reqs(tok, B_TEXTS),
                on_result=lambda r: got_b.__setitem__(r.row_id, r),
                priority=0,
                seq=1,
            ),
        ],
        on_job_done=lambda c, o: done.append((c.job_id, o)),
    )
    assert state == "completed"
    assert dict(done) == {"A": "completed", "B": "completed"}
    assert b.free_page_count == free0, "leaked pages (incl. prefix)"
    # the shared prefix must have saved prefill work
    naive = sum(len(tok.encode(t)) for t in a_texts + B_TEXTS)
    assert b.prefill_tokens < naive, (b.prefill_tokens, naive)
    return (
        {i: r.token_ids for i, r in got_a.items()},
        {i: r.token_ids for i, r in got_b.items()},
    )


def test_composed_fp_exact_vs_plain(byte_tok):
    """fp leg: the full composition == solo with every
    exactness-preserving feature off."""
    tok = byte_tok
    a_texts = [PREFIX + s for s in A_SUFFIXES]
    on_a, on_b = _cobatch(_ecfg(), tok)
    plain = _ecfg(
        prefix_cache=False, decode_lookahead=1, prefill_chunk=512
    )
    assert on_a == _solo(plain, tok, a_texts)
    assert on_b == _solo(plain, tok, B_TEXTS)


@pytest.mark.slow  # second full composed-stack run differing from the
# fp leg only in kv_quantize; int8 KV exactness is pinned fast by
# test_kv_int8.py and the fp composition leg stays tier-1
def test_composed_int8_exact_vs_same_config_solo(byte_tok):
    """int8 leg: co-batching is a pure scheduling change — exact vs
    solo under the same composed config and KV read pattern."""
    tok = byte_tok
    a_texts = [PREFIX + s for s in A_SUFFIXES]
    ecfg = _ecfg(kv_quantize="int8")
    on_a, on_b = _cobatch(ecfg, tok)
    assert on_a == _solo(ecfg, tok, a_texts)
    assert on_b == _solo(ecfg, tok, B_TEXTS)
