"""``reference/qwen3_moe.py`` against the program in float32 (through the
paged cache on tiny-moe, over whole sequences on the 128-expert preset)
and its routed MLP against a per-token loop written out by hand.

Tolerance 2e-4 of the largest logit, as for the dense reference: both
sides compute in float32 and differ in summation order only (measured
here: about 1e-6), and at that level no routing flips."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import qwen3_moe
from perfbench.sut import System
from tests.perfbench.routed_systems import (
    ForwardSystem, preset, program_logits, published_keys,
)
from sutro_tpu.models import transformer

TOL = 2e-4


def test_reference_agrees_with_the_program_through_the_cache():
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    mcfg = MODEL_CONFIGS["tiny-moe"]
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32",
    )
    runner = ModelRunner(mcfg, ecfg)
    sut = object.__new__(System)
    sut.ecfg, sut.engine_key = ecfg, "tiny-moe"
    sut.engine = types.SimpleNamespace(_runner_cache={"tiny-moe": (runner, None)})
    ids = np.random.default_rng(7).integers(0, 256, (3, 26))
    got = sut.logits_through_cache(ids, 21, 5)      # crosses a page boundary
    assert got.shape == (3, 6, mcfg.vocab_size)
    for s, seq in enumerate(ids):
        want = np.asarray(qwen3_moe.logits_at(
            published_keys(mcfg), runner.params, seq, list(range(20, 26))
        ))
        assert correctness.position_errors(got[s], want).max() < TOL
        # one sequence alone goes the same way as one of several
        if s == 0:
            alone = sut.logits_through_cache(seq, 21, 5)
            assert np.array_equal(alone, got[0])


def test_reference_agrees_with_the_program_at_128_experts():
    mcfg = preset(hidden=128, layers=2, vocab=512)
    params = transformer.init_params(mcfg, jax.random.PRNGKey(3), "float32")
    ids = np.random.default_rng(5).integers(0, 256, 200).astype(np.int32)
    got = np.asarray(program_logits(mcfg, params, jnp.asarray(ids)))
    want, ties = qwen3_moe.logits_and_near_ties(
        published_keys(mcfg), params, ids, list(range(200))
    )
    assert correctness.position_errors(got, np.asarray(want)).max() < TOL
    # 8th against 9th of 128 logits is often close: the count is not idle
    ties = np.asarray(ties)
    assert ties.shape == (200,) and 0 < ties.sum() < 2 * 200


def loop_mlp(x, router, gate, up, down, k, norm):
    """The published MLP, one token at a time, in float64."""
    out = np.zeros_like(x)
    for t, xt in enumerate(x):
        logits = xt @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:k]
        weights = p[chosen] / (p[chosen].sum() if norm else 1.0)
        for e, w in zip(chosen, weights):
            g = xt @ gate[e]
            out[t] += w * ((g / (1.0 + np.exp(-g))) * (xt @ up[e])) @ down[e]
    return out


@pytest.mark.parametrize("norm", [True, False], ids=["renormalised", "as_softmax"])
def test_routed_mlp_is_the_published_one(norm):
    rng = np.random.default_rng(11)
    T, H, E, F, K = 12, 32, 16, 24, 4
    x = rng.standard_normal((T, H))
    router = rng.standard_normal((H, E)) * 0.1
    gate, up = rng.standard_normal((2, E, H, F)) * H ** -0.5
    down = rng.standard_normal((E, F, H)) * F ** -0.5
    layers = {
        "router": jnp.asarray(router[None], jnp.float32),
        "we_gate": jnp.asarray(gate[None], jnp.float32),
        "we_up": jnp.asarray(up[None], jnp.float32),
        "we_down": jnp.asarray(down[None], jnp.float32),
    }
    dims = {"experts": E, "top_k": K, "norm_topk": norm}
    with jax.default_matmul_precision("highest"):
        got, gap = qwen3_moe.routed_mlp(dims, layers, 0, jnp.asarray(x, jnp.float32))
    want = loop_mlp(x, router, gate, up, down, K, norm)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    # the two conventions differ by the chosen experts' share of the softmax
    other = loop_mlp(x, router, gate, up, down, K, not norm)
    assert np.abs(other - want).max() > 0.1 * np.abs(want).max()
    logits = x @ router
    ranked = -np.sort(-logits, axis=-1)
    want_gap = (ranked[:, K - 1] - ranked[:, K]) / logits.std(axis=-1)
    assert np.allclose(np.asarray(gap), want_gap, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("change", [
    {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
    {"shared_expert_intermediate_size": 64}, {"norm_topk_prob": None},
    {"num_experts_per_tok": 0},
], ids=lambda c: next(iter(c)))
def test_what_the_reference_does_not_follow_it_refuses(change):
    keys = published_keys(preset(hidden=128, layers=1))
    for k, v in change.items():
        if v is None:
            del keys[k]
        else:
            keys[k] = v
    with pytest.raises(ValueError):
        qwen3_moe.moe_dims_of(keys)
