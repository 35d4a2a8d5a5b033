"""The plain float32 reference against models/transformer.py (XLA path,
float32) at a tiny size: prefill, then decode steps through the paged
cache, exactly as the chip run compares them at full width.

Tolerance 2e-4 of the largest logit: both sides compute in float32 and
differ only in summation order and in the one-pass vs. cached attention
(measured here: about 1e-6). A wrong RoPE, a dropped QK-norm, a head
applied to the wrong position or a cache page read out of place gives an
error near 1; bf16 anywhere gives about 1e-2.
"""

import dataclasses
import types

import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import qwen3_dense
from perfbench.sut import System

TOL = 2e-4


def published_keys(m):
    return {
        "hidden_size": m.hidden_size, "num_hidden_layers": m.num_layers,
        "num_attention_heads": m.num_heads, "num_key_value_heads": m.num_kv_heads,
        "head_dim": m.head_dim, "intermediate_size": m.intermediate_size,
        "vocab_size": m.vocab_size, "tie_word_embeddings": m.tie_embeddings,
        "rms_norm_eps": m.norm_eps, "rope_theta": m.rope_theta,
    }


def system_around(runner, ecfg, key):
    """``System`` without an engine: just what the logits path reads."""
    s = object.__new__(System)
    s.ecfg, s.engine_key = ecfg, key
    s.engine = types.SimpleNamespace(_runner_cache={key: (runner, None)})
    return s


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_reference_agrees_with_the_program_through_the_cache(tiny_ecfg, tied):
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    mcfg = dataclasses.replace(MODEL_CONFIGS["tiny-dense"], tie_embeddings=tied)
    runner = ModelRunner(mcfg, tiny_ecfg)
    assert ("lm_head" in runner.params) == (not tied)
    sut = system_around(runner, tiny_ecfg, "tiny-dense")
    n_prefill, n_decode = 21, 5          # crosses a page boundary (pages of 8)
    ids = np.random.default_rng(7).integers(0, 256, n_prefill + n_decode)
    got = sut.logits_through_cache(ids, n_prefill, n_decode)
    positions = list(range(n_prefill - 1, n_prefill + n_decode))
    want = np.asarray(
        qwen3_dense.logits_at(published_keys(mcfg), runner.params, ids, positions)
    )
    assert got.shape == want.shape == (1 + n_decode, mcfg.vocab_size)
    for j in range(len(positions)):
        err = np.abs(got[j] - want[j]).max() / np.abs(want[j]).max()
        assert err < TOL, (j, err)


def test_reference_runs_on_weights_sharded_over_a_mesh(tiny_ecfg, eight_devices):
    """The four-chip cell's path at a tiny size on virtual devices: a
    tp=2 runner (weights and pool born sharded), the system's logits
    through its cache, and the reference on the sharded weights as they
    are."""
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    mcfg = dataclasses.replace(MODEL_CONFIGS["tiny-dense"], tie_embeddings=False)
    ecfg = dataclasses.replace(tiny_ecfg, tp=2)
    runner = ModelRunner(mcfg, ecfg)
    assert runner.n_devices == 2
    sut = system_around(runner, ecfg, "tiny-dense")
    ids = np.random.default_rng(9).integers(0, 256, 24)
    got = sut.logits_through_cache(ids, 19, 5)
    want = np.asarray(qwen3_dense.logits_at(
        published_keys(mcfg), runner.params, ids, list(range(18, 24))
    ))
    for j in range(6):
        err = np.abs(got[j] - want[j]).max() / np.abs(want[j]).max()
        assert err < TOL, (j, err)


def test_a_wrong_position_is_caught(tiny_ecfg):
    """The tolerance is tight enough to tell neighbouring positions
    apart: the reference scored one position off does not pass."""
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    mcfg = MODEL_CONFIGS["tiny-dense"]
    runner = ModelRunner(mcfg, tiny_ecfg)
    sut = system_around(runner, tiny_ecfg, "tiny-dense")
    ids = np.random.default_rng(8).integers(0, 256, 20)
    got = sut.logits_through_cache(ids, 16, 4)
    off = np.asarray(qwen3_dense.logits_at(
        published_keys(mcfg), runner.params, ids, [14]
    ))
    err = np.abs(got[0] - off[0]).max() / np.abs(off[0]).max()
    assert err > 100 * TOL


def test_chip_tolerances_are_written_with_the_check():
    import json
    from pathlib import Path

    table = json.loads(
        (Path(correctness.__file__).parent / "reference/tolerance.json").read_text()
    )
    assert 0 < table["float32"] < table["bfloat16"] <= 0.1
    assert correctness.N_PREFILL == 192 and correctness.N_DECODE == 8
