"""FSM fast-forward ("jump decoding"): scaffold regions where the
schema forces exactly one next token are peeled host-side and committed
through ONE parallel verify forward (runner.verify_candidates) instead of
step-by-step speculative windows that reject their unmasked samples
there. Exactness contract: token_ids and finish_reason identical to
the every-step-masked path (decode_multi_step=1) AND to the
speculative-window path with fast-forward disabled."""

import dataclasses
import json

import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.constrain import schema_constraint_factory
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.models.configs import MODEL_CONFIGS

# scaffold-heavy: long const-ish required keys, enum leaves — most of
# the output is FSM-forced
SCHEMA = {
    "type": "object",
    "properties": {
        "classification_result": {
            "type": "string",
            "enum": ["positive", "negative"],
        },
        "confidence_level": {
            "type": "string",
            "enum": ["high", "low"],
        },
    },
    "required": ["classification_result", "confidence_level"],
}


def _run(byte_tok, multi, ff, texts=None, extra_plain=0):
    ecfg = EngineConfig(
        kv_page_size=8,
        max_pages_per_seq=32,
        max_model_len=256,
        decode_batch_size=4,
        use_pallas=False,
        param_dtype="float32",
        activation_dtype="float32",
        decode_multi_step=multi,
        constrain_fastforward=ff,
    )
    runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
    factory = schema_constraint_factory(SCHEMA, byte_tok)
    texts = texts or ["first row", "second", "third one"]
    reqs = [
        GenRequest(
            row_id=i,
            prompt_ids=np.array(byte_tok.encode(t), np.int32),
            max_new_tokens=80,
            temperature=0.0,
            constraint=factory(),
        )
        for i, t in enumerate(texts)
    ]
    for j in range(extra_plain):  # unconstrained greedy riders
        reqs.append(
            GenRequest(
                row_id=100 + j,
                prompt_ids=np.array(
                    byte_tok.encode(f"plain rider {j}"), np.int32
                ),
                max_new_tokens=12,
                temperature=0.0,
            )
        )
    b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
    res = {}
    assert (
        b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
        == "completed"
    )
    return b, {
        i: (tuple(r.token_ids), r.finish_reason) for i, r in res.items()
    }


def test_fastforward_exact_vs_masked_and_window(byte_tok):
    b_ff, ff = _run(byte_tok, 8, 16)
    assert b_ff.ff_forced > 0, "scaffold schema never fast-forwarded"
    _, masked = _run(byte_tok, 1, 0)
    _, window = _run(byte_tok, 8, 0)
    assert ff == masked
    assert ff == window
    # outputs are complete schema-valid JSON
    for toks, _ in ff.values():
        parsed = json.loads(byte_tok.decode(list(toks)))
        assert parsed["classification_result"] in (
            "positive", "negative",
        )
        assert parsed["confidence_level"] in ("high", "low")


def test_const_schema_needs_zero_windows(byte_tok, monkeypatch):
    """A fully-forced schema (const) commits its entire output through
    fast-forward verifies: ZERO speculative-window dispatches — the
    strongest contrast with the per-row rejection recovery the window
    path needs for the same schema."""
    from sutro_tpu.engine.runner import ModelRunner as MR

    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
        decode_batch_size=4, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", decode_multi_step=8,
        constrain_fastforward=16,
    )
    runner = MR(MODEL_CONFIGS["tiny-dense"], ecfg)
    calls = {"window": 0}
    orig = runner.decode_window

    def window(*a, **kw):
        calls["window"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(runner, "decode_window", window)
    factory = schema_constraint_factory(
        {"const": "zqxzqxzqxzqx"}, byte_tok
    )
    b = ContinuousBatcher(
        runner, stop_ids=byte_tok.stop_ids(),
        token_bytes=byte_tok.token_bytes,
    )
    res = {}
    assert (
        b.run(
            [
                GenRequest(
                    row_id=0,
                    prompt_ids=np.array(
                        byte_tok.encode("adv"), np.int32
                    ),
                    max_new_tokens=40,
                    temperature=0.0,
                    constraint=factory(),
                )
            ],
            on_result=lambda r: res.__setitem__(r.row_id, r),
        )
        == "completed"
    )
    out = b"".join(byte_tok.token_bytes(t) for t in res[0].token_ids)
    assert json.loads(out.decode()) == "zqxzqxzqxzqx"
    assert res[0].finish_reason == "schema_complete"
    assert calls["window"] == 0, calls
    assert b.ff_forced >= 10


def test_fastforward_with_unconstrained_riders(byte_tok):
    """Greedy unconstrained rows ride the verify dispatch as
    draft_len-0 plain greedy steps — their outputs must equal a run
    with fast-forward off."""
    b_ff, ff = _run(byte_tok, 8, 16, extra_plain=1)
    assert b_ff.ff_forced > 0
    _, off = _run(byte_tok, 8, 0, extra_plain=1)
    assert ff == off
    assert any(i >= 100 for i in ff)  # the rider completed


def test_fastforward_respects_budget_cap(byte_tok):
    """A tight max_new_tokens still yields complete JSON (the peel
    honors the budget-aware closure masks step by step)."""
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
        decode_batch_size=4, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", decode_multi_step=8,
        constrain_fastforward=16,
    )
    runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
    factory = schema_constraint_factory(SCHEMA, byte_tok)
    c = factory()
    need = c.min_tokens() if hasattr(c, "min_tokens") else 0
    reqs = [
        GenRequest(
            row_id=0,
            prompt_ids=np.array(byte_tok.encode("x"), np.int32),
            max_new_tokens=max(need, 1),  # engine raises to feasible
            temperature=0.0,
            constraint=factory(),
        )
    ]
    b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
    res = {}
    assert (
        b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
        == "completed"
    )
    parsed = json.loads(byte_tok.decode(list(res[0].token_ids)))
    assert parsed["classification_result"] in ("positive", "negative")


def test_mixed_freetext_scaffold_handoff(byte_tok):
    """A schema with a free-text field then enum scaffold exercises the
    window <-> fast-forward handoff: the window samples the string body
    (and its rejections flag rows), fast-forward commits the scaffold
    (flagged SINGLETON rows are candidates — the peel is their masked
    step). Outputs must equal the every-step-masked path exactly."""
    schema = {
        "type": "object",
        "properties": {
            "note": {"type": "string", "maxLength": 20},
            "label": {"type": "string", "enum": ["alpha", "beta"]},
        },
        "required": ["note", "label"],
    }

    def run(multi, ff):
        ecfg = EngineConfig(
            kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
            decode_batch_size=4, use_pallas=False,
            param_dtype="float32", activation_dtype="float32",
            decode_multi_step=multi, constrain_fastforward=ff,
        )
        runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
        factory = schema_constraint_factory(schema, byte_tok)
        reqs = [
            GenRequest(
                row_id=i,
                prompt_ids=np.array(byte_tok.encode(t), np.int32),
                max_new_tokens=80,
                temperature=0.0,
                constraint=factory(),
            )
            for i, t in enumerate(["first row", "second", "third one"])
        ]
        b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
        res = {}
        assert (
            b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
            == "completed"
        )
        return b, {
            i: (tuple(r.token_ids), r.finish_reason)
            for i, r in res.items()
        }

    b_ff, ff = run(8, 16)
    assert b_ff.ff_forced > 0
    _, masked = run(1, 0)
    assert ff == masked
    for toks, _ in ff.values():
        parsed = json.loads(byte_tok.decode(list(toks)))
        assert parsed["label"] in ("alpha", "beta")


class _MergedTok:
    """Synthetic BPE-style tokenizer: byte ids 0..255 + specials (as
    ByteTokenizer) + MERGED multi-byte tokens for scaffold substrings.
    A forced byte path then admits MANY tokenizations (every prefix
    token is mask-legal), which is exactly the real-vocab regime the
    masked-candidate verification handles token-exactly."""

    def __init__(self, vocab_size):
        from sutro_tpu.engine.tokenizer import ByteTokenizer

        self._bt = ByteTokenizer(vocab_size=vocab_size)
        self.vocab_size = vocab_size
        base = 256 + len(self._bt.SPECIALS)
        self.merged = {
            base + 0: b'{"classification_result"',
            base + 1: b'":"',
            base + 2: b"positive",
            base + 3: b"negative",
            base + 4: b'","confidence_level":"',
            base + 5: b'"}',
            base + 6: b"classific",
            base + 7: b"ation_result",
        }
        self.eos_id = self._bt.eos_id

    def encode(self, text):
        return self._bt.encode(text)

    def decode(self, ids):
        return b"".join(self.token_bytes(t) for t in ids).decode(
            errors="replace"
        )

    def token_bytes(self, tid):
        if tid in self.merged:
            return self.merged[tid]
        return self._bt.token_bytes(tid)

    def stop_ids(self):
        return self._bt.stop_ids()


def test_fastforward_bpe_style_merged_vocab(byte_tok):
    """Under a merged (BPE-style) vocab the forced byte path admits
    every prefix tokenization, so masks are NOT singletons — the
    masked-candidate verification must still produce tokens IDENTICAL
    to the every-step-masked path, while committing multi-token jumps
    (ff_forced > 0)."""
    tok = _MergedTok(MODEL_CONFIGS["tiny-dense"].vocab_size)

    def run(multi, ff):
        ecfg = EngineConfig(
            kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
            decode_batch_size=4, use_pallas=False,
            param_dtype="float32", activation_dtype="float32",
            decode_multi_step=multi, constrain_fastforward=ff,
        )
        runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
        factory = schema_constraint_factory(SCHEMA, tok)
        reqs = [
            GenRequest(
                row_id=i,
                prompt_ids=np.array(tok.encode(t), np.int32),
                max_new_tokens=80,
                temperature=0.0,
                constraint=factory(),
            )
            for i, t in enumerate(["first row", "second", "third one"])
        ]
        b = ContinuousBatcher(runner, stop_ids=tok.stop_ids())
        res = {}
        assert (
            b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
            == "completed"
        )
        return b, {
            i: (tuple(r.token_ids), r.finish_reason)
            for i, r in res.items()
        }

    b_ff, ff = run(8, 16)
    assert b_ff.ff_forced > 0, "merged vocab never fast-forwarded"
    _, masked = run(1, 0)
    assert ff == masked, "BPE-style jump diverged from the masked path"
    for toks, _ in ff.values():
        parsed = json.loads(tok.decode(list(toks)))
        assert parsed["classification_result"] in (
            "positive", "negative",
        )


def test_unconstrained_riders_in_fastforward_dispatch(byte_tok):
    """Unconstrained greedy rows beside constrained ones ride the
    fast-forward dispatch as plain greedy steps (position 0 of the
    verify forward): outputs identical to a run with fast-forward
    off."""

    def run(ff):
        ecfg = EngineConfig(
            kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
            decode_batch_size=4, use_pallas=False,
            param_dtype="float32", activation_dtype="float32",
            decode_multi_step=8, constrain_fastforward=ff,
        )
        runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
        factory = schema_constraint_factory(SCHEMA, byte_tok)
        reqs = [
            GenRequest(
                row_id=i,
                prompt_ids=np.array(byte_tok.encode(t), np.int32),
                max_new_tokens=60,
                temperature=0.0,
                constraint=factory(),
            )
            for i, t in enumerate(["first row", "second"])
        ]
        for j, t in enumerate(
            ["abc abc abc abc abc", "the cat sat on the mat the cat"]
        ):
            reqs.append(
                GenRequest(
                    row_id=100 + j,
                    prompt_ids=np.array(byte_tok.encode(t), np.int32),
                    max_new_tokens=24,
                    temperature=0.0,
                )
            )
        b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
        res = {}
        assert (
            b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
            == "completed"
        )
        return b, {
            i: (tuple(r.token_ids), r.finish_reason)
            for i, r in res.items()
        }

    b_on, on = run(16)
    _, off = run(0)
    assert on == off, "riding the verify dispatch changed outputs"
    assert b_on.ff_forced > 0
    assert all(len(on[100 + j][0]) > 1 for j in range(2))
