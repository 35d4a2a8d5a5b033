"""Pipelined fused-window decode (scheduler lookahead) correctness.

The pipelined path dispatches window k+1 off window k's device-resident
tokens before window k's results reach the host. For greedy decoding the
sampled tokens are rng-independent, so every row's output must be
IDENTICAL to the same path at a depth of one (lookahead=1: dispatch and
fetch in one iteration) and to single steps (decode_multi_step=1, which
shares no window code) — including across slot reuse (rows finishing mid-pipeline and new rows admitted into their
slots) and constrained rows forcing a mid-job drain.
"""

import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models.configs import MODEL_CONFIGS


def _run(lookahead: int, reqs_fn, batch=2, multi=4, **ecfg_kw):
    mcfg = MODEL_CONFIGS["tiny-dense"]
    kw = dict(
        kv_page_size=8,
        max_pages_per_seq=8,
        decode_batch_size=batch,
        max_model_len=64,
        use_pallas=False,
        param_dtype="float32",
        decode_multi_step=multi,
        decode_lookahead=lookahead,
    )
    kw.update(ecfg_kw)
    ecfg = EngineConfig(**kw)
    tok = ByteTokenizer(vocab_size=mcfg.vocab_size)
    b = ContinuousBatcher(ModelRunner(mcfg, ecfg), stop_ids=tok.stop_ids())
    res = {}
    status = b.run(reqs_fn(tok), on_result=lambda r: res.__setitem__(r.row_id, r))
    assert status == "completed"
    return res


def _greedy_reqs(tok, texts, max_new):
    return [
        GenRequest(
            row_id=i,
            prompt_ids=np.array(tok.encode(t), np.int32),
            max_new_tokens=mn,
            temperature=0.0,
        )
        for i, (t, mn) in enumerate(zip(texts, max_new))
    ]


_STAGGERED = {}


def _staggered(lookahead, multi=4):
    """One run of the staggered greedy job per configuration."""
    texts = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot"]
    # staggered budgets force rows to finish mid-pipeline and slots to be
    # reused while windows for the old occupants are still in flight
    max_new = [5, 17, 9, 23, 7, 13]
    if (lookahead, multi) not in _STAGGERED:
        res = _run(
            lookahead, lambda tok: _greedy_reqs(tok, texts, max_new),
            multi=multi,
        )
        assert set(res) == set(range(len(texts)))
        _STAGGERED[lookahead, multi] = {
            i: (r.token_ids, r.finish_reason) for i, r in res.items()
        }
    return _STAGGERED[lookahead, multi]


@pytest.mark.parametrize("depth", [2, 3])
def test_pipelined_matches_sync_greedy(depth):
    piped = _staggered(depth)
    assert piped == _staggered(1), "against the same path at depth one"
    assert piped == _staggered(1, multi=1), "against single steps"


def test_pipelined_capacity_bounded():
    # tiny page budget: capacity stops lookahead dispatches early and the
    # single-step fallback finishes the tails — outputs must still match
    texts = ["k", "longer prompt here", "mid"]
    max_new = [30, 30, 30]

    def reqs(tok):
        return _greedy_reqs(tok, texts, max_new)

    sync = _run(1, reqs, batch=2, multi=8, max_pages_per_seq=6,
                max_model_len=48)
    piped = _run(2, reqs, batch=2, multi=8, max_pages_per_seq=6,
                 max_model_len=48)
    for i in sync:
        assert piped[i].token_ids == sync[i].token_ids, f"row {i}"


class _PrefixConstraint:
    """Requires the first two tokens to be 65, then anything; complete
    after 4 tokens. Exercises the speculative-window/drain interplay."""

    def __init__(self, vocab):
        self.vocab = vocab
        self.n = 0

    def allowed_tokens(self, remaining=None):
        m = np.ones((self.vocab,), bool)
        if self.n < 2:
            m[:] = False
            m[65] = True
        return m

    def advance(self, token_id):
        self.n += 1

    def is_complete(self):
        return self.n >= 4


def test_pipelined_drains_for_constrained_rows():
    # unconstrained rows start a pipeline; a constrained row arriving in
    # a later admission forces a drain, then the speculative/masked path
    # runs — everything must still complete with correct budgets
    def reqs(tok):
        rs = _greedy_reqs(
            tok, ["aaa", "bbb", "ccc", "ddd"], [12, 12, 12, 12]
        )
        rs.append(
            GenRequest(
                row_id=4,
                prompt_ids=np.array(tok.encode("zz"), np.int32),
                max_new_tokens=8,
                temperature=0.0,
                constraint=_PrefixConstraint(tok.vocab_size),
            )
        )
        return rs

    res = _run(2, reqs)
    assert set(res) == set(range(5))
    for i in range(4):
        assert len(res[i].token_ids) <= 12
    r4 = res[4]
    assert r4.token_ids[:2] == [65, 65]
    assert r4.finish_reason in ("schema_complete", "stop", "length")


def test_pipelined_sampled_smoke():
    # non-greedy rows still complete with the right budgets (token
    # equality is not required: rng key order differs by pipelining)
    def reqs(tok):
        return [
            GenRequest(
                row_id=i,
                prompt_ids=np.array(tok.encode(t), np.int32),
                max_new_tokens=10,
                temperature=0.8,
            )
            for i, t in enumerate(["one", "two", "three"])
        ]

    res = _run(2, reqs)
    assert set(res) == {0, 1, 2}
    for r in res.values():
        assert 0 < len(r.token_ids) <= 10
