"""Solar Open 2's layers (``tiny-solar-kda``: gated NoPE GQA, three Kimi
Delta Attention layers, GQA, KDA; 16 experts top-4 of which 4 are held,
a shared expert) against the plain float32 reference
(``perfbench/reference/kda_gqa_moe.py``: the delta rule ONE TOKEN AT A
TIME, no chunk form, no triangular solve) on seeded random weights.

What is compared is LOGITS, never tokens. Two tolerances, each with its
reason:

- float32 ``TOL`` = 2e-4 of the largest logit: both sides compute in
  float32 and differ in the order of sums and in the chunk form's
  algebra (a solve in place of a recurrence); measured about 2e-6. A
  bfloat16 recurrence (activations and state in bfloat16) under the
  same float32 weights reads 1e-2 and more, fifty times over it
  (``test_a_bfloat16_recurrence_under_float32_weights_fails``).
- bfloat16 ``TOL_BF16`` = 0.06, the benchmark's own
  (``perfbench/reference/tolerance.json``): weights, activations and
  the stored state rounded to 8 bits of mantissa through six layers;
  measured 0.012-0.023.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import kda_gqa_moe
from sutro_tpu.engine import kvcache
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models import transformer
from sutro_tpu.ops import moe
from tests.solar_kda_common import (
    KEYS, MCFG, engine, err, sequence, table_of, want,
)

TOL, TOL_BF16 = 2e-4, 0.06
N_PRE, N_DEC = 45, 6


def through_the_cache(runner, seq):
    """Prefill ``seq[:N_PRE]`` (one chunk, scan chunks of 8), then
    ``N_DEC`` single steps through the cache, as ``perfbench/sut.py``
    takes them: logits [1 + N_DEC, V]."""
    table = table_of(*range(1, 9))

    @jax.jit
    def step(params, cache, tok, past_len, page_table):
        logits, _, (k, v) = runner._trunk_decode(
            params, cache, tok, past_len[:, None], past_len, page_table
        )
        cache = write_kv(cache, k, v, page_table, past_len,
                         jnp.ones((1,), jnp.int32))
        return logits[0, 0].astype(jnp.float32), cache

    out = [np.asarray(runner.prefill(seq[:N_PRE], table), np.float32)]
    cache = runner.cache
    for j in range(N_DEC):
        lg, cache = step(
            runner.params, cache, jnp.asarray(seq[None, N_PRE + j : N_PRE + j + 1]),
            jnp.asarray([N_PRE + j], jnp.int32), jnp.asarray(table[None]),
        )
        out.append(np.asarray(lg))
    return np.stack(out)


@pytest.fixture(scope="module")
def readings():
    """The system through the cache in float32, in bfloat16 and with a
    bfloat16 recurrence under float32 weights, each against the
    reference on ITS weights: one set of programs for three tests."""
    seq = sequence(1, N_PRE + N_DEC)
    positions = range(N_PRE - 1, N_PRE + N_DEC)
    out = {}
    for name, kw in (
        ("float32", {}),
        ("bfloat16", dict(param_dtype="bfloat16", activation_dtype="bfloat16")),
        ("mixed", dict(activation_dtype="bfloat16")),
    ):
        # one prefill program a runner: the chunked path is
        # tests/test_solar_kda_paths.py's
        r = ModelRunner(MCFG, engine(prefill_chunk=64, **kw), num_pages=17)
        out[name] = err(through_the_cache(r, seq), want(r, seq, positions))
    return out


def test_float32_prefill_then_decode_through_the_cache_is_the_reference(readings):
    assert readings["float32"] < TOL


def test_bfloat16_is_inside_the_benchmarks_tolerance(readings):
    assert 1e-3 < readings["bfloat16"] < TOL_BF16


def test_a_bfloat16_recurrence_under_float32_weights_fails(readings):
    assert readings["mixed"] > 10 * TOL


@pytest.mark.parametrize("variant", ["no_decay", "no_delta"])
def test_both_mechanism_controls_fail(variant):
    """The reference computing ANOTHER model (no decay; no ``- beta k
    k^T`` term) is far from itself: the check can tell the mechanism."""
    r = ModelRunner(MCFG, engine(), num_pages=2)
    seq = sequence(2, 30)
    real = want(r, seq, range(20, 30))
    other = want(r, seq, range(20, 30), variant=variant)
    assert err(other, real) > 0.1


# -- the chunk form against the token scan ---------------------------------------


def _tokens(T, H=3, dk=8, seed=0, beta_hi=False, g_lo=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = transformer.l2_norm(jax.random.normal(ks[0], (1, T, H, dk))) * dk ** -0.5
    k = transformer.l2_norm(jax.random.normal(ks[1], (1, T, H, dk)))
    v = jax.random.normal(ks[2], (1, T, H, dk))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (1, T, H)))
    g = -jnp.exp(jax.random.normal(ks[4], (1, T, H, dk)) - 2.0)
    if beta_hi:
        beta = jnp.full_like(beta, 1.999)
    if g_lo:
        g = jnp.where(jax.random.bernoulli(ks[5], 0.3, g.shape), -30.0, g)
    return q, k, v, g, beta


@pytest.mark.parametrize("T", [1, 63, 64, 65, 200])
def test_the_chunk_form_is_the_token_scan_at_chunk_edges(T):
    q, k, v, g, beta = _tokens(T, seed=T)
    S0 = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 3, 8)) * 0.3
    o, S = transformer.kda_chunked(q, k, v, g, beta, S0, 64)
    with jax.default_matmul_precision("highest"):
        o_ref, S_ref, _ = kda_gqa_moe.kda_scan(
            q[0], k[0], v[0], beta[0], g[0], S0=jnp.moveaxis(S0[0], 1, 0)
        )
    scale = float(jnp.abs(o_ref).max())
    assert float(jnp.abs(o[0] - o_ref).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(jnp.moveaxis(S[0], 1, 0) - S_ref).max()) < 2e-5


def test_beta_near_two_and_a_log_decay_of_minus_thirty_stay_finite():
    """Eigenvalues at -1 and channels that forget everything in one
    token: every decay is pairwise (at most 1), nothing divides by a
    cumulative decay, and the chunk form stays the scan."""
    q, k, v, g, beta = _tokens(130, seed=3, beta_hi=True, g_lo=True)
    S0 = jnp.zeros((1, 8, 3, 8))
    o, S = transformer.kda_chunked(q, k, v, g, beta, S0, 64)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(S)))
    with jax.default_matmul_precision("highest"):
        o_ref, _, _ = kda_gqa_moe.kda_scan(q[0], k[0], v[0], beta[0], g[0])
    assert float(jnp.abs(o[0] - o_ref).max()) < 1e-4 * float(jnp.abs(o_ref).max())


def test_one_solve_serves_every_accepted_length_of_a_window():
    """``S_n`` committed from a window's ``(g, k, u)`` for n = 0..W is
    the scan's state after n tokens, from a committed ``S_0``."""
    W, H, dk = 8, 4, 16
    cfg = dataclasses.replace(MCFG, name="kda: a window")
    q, k, v, g, beta = _tokens(W, H=H, dk=dk, seed=5)
    pool = jax.random.normal(jax.random.PRNGKey(6), (1, 3, dk, H * dk)) * 0.3
    slots = jnp.asarray([2], jnp.int32)
    flat = lambda a: a.reshape(1, W, H * dk)  # noqa: E731
    o, u = transformer.kda_pending(
        cfg, pool, 0, slots, jnp.asarray([False]), flat(q), flat(v), beta,
        flat(g), flat(k), jnp.zeros((1, W, H * dk)), 0,
    )
    S0 = jnp.moveaxis(pool[0, 2].reshape(dk, H, dk), 1, 0)     # [H, dk, dv]
    with jax.default_matmul_precision("highest"):
        o_ref, _, every = kda_gqa_moe.kda_scan(
            q[0], k[0], v[0], beta[0], g[0], S0=S0
        )
    assert float(jnp.abs(o.reshape(W, H, dk) - o_ref).max()) < 2e-5
    chunk = {
        "g": flat(g)[None], "k": flat(k)[None], "u": u[None],
        "conv": jnp.zeros((1, 1, 3 + W, 3 * H * dk)),
    }
    for n in range(W + 1):
        new, _ = kvcache._advance_kda(
            pool, chunk, slots, jnp.asarray([False]),
            jnp.asarray([n], jnp.int32), 3,
        )
        got = jnp.moveaxis(new[0, 2].reshape(dk, H, dk), 1, 0)
        wanted = S0 if n == 0 else every[n - 1]
        assert float(jnp.abs(got - wanted).max()) < 2e-5, n
        # the other slots are not touched
        assert bool(jnp.all(new[0, 1] == pool[0, 1]))


# -- the gate and the shares -------------------------------------------------------


def test_the_attention_gate_is_a_sigmoid_of_the_layers_input_a_channel():
    """``out = (concat_h o_h * sigmoid(x W_gate)) W_o``: with ``W_o`` the
    identity (the preset's heads x head_dim is its hidden size) the gated
    layer's output over the ungated one's is the gate."""
    assert MCFG.attn_gate and MCFG.q_size == MCFG.hidden_size
    params = transformer.init_params(MCFG, jax.random.PRNGKey(2), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"]["attn"].items()}
    assert lp["w_attn_gate"].shape == (MCFG.hidden_size, MCFG.q_size)
    lp["wo"] = jnp.eye(MCFG.q_size)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 7, MCFG.hidden_size))
    args = dict(
        positions=jnp.tile(jnp.arange(7)[None], (2, 1)),
        valid_len=jnp.asarray([7, 7]), window=jnp.int32(0),
        theta=jnp.float32(MCFG.rope_theta),
    )
    gated, _ = transformer.attention_mixer(MCFG, lp, x, **args)
    plain, _ = transformer.attention_mixer(
        MCFG, {k: v for k, v in lp.items() if k != "w_attn_gate"}, x, **args
    )
    gate = jax.nn.sigmoid(x @ lp["w_attn_gate"])
    assert float(jnp.abs(gated - plain * gate).max()) < 1e-5
    assert float(jnp.abs(gated - plain).max()) > 1e-2       # and it bites


def test_the_shares_add_up_to_the_uncut_layer():
    """What the four shares of four experts compute, with the shared
    expert counted ONCE, is what the uncut reference gives for the whole
    layer (the published sixteen shares of twenty, at the preset's
    size)."""
    cfg = dataclasses.replace(MCFG, name="tiny-solar-kda: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    keys = dict(KEYS, n_routed_experts=cfg.moe_experts)
    d = kda_gqa_moe.dims_of(keys)
    moe_l = params["layers"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 5, cfg.hidden_size))
    index, held = 2, MCFG.experts_held
    flat = x.reshape(15, -1)
    with jax.default_matmul_precision("highest"):
        whole, _ = kda_gqa_moe.routed_ffn(d, moe_l, index, flat, shared=True)
        only_shared = kda_gqa_moe.swiglu(
            flat, moe_l["shared_gate"][index], moe_l["shared_up"][index],
            moe_l["shared_down"][index],
        )
    total = np.zeros((3, 5, cfg.hidden_size), np.float32)
    counted = 0
    for first in range(0, cfg.moe_experts, held):
        share_cfg = dataclasses.replace(
            cfg, moe_experts_held=held, moe_first_expert=first
        )
        lp = {k: v[index] for k, v in moe_l.items()}
        y, counts = moe.moe_mlp(
            x, lp["router"], lp["we_gate"][first : first + held],
            lp["we_up"][first : first + held],
            lp["we_down"][first : first + held],
            top_k=cfg.moe_top_k, activation=cfg.activation,
            first_expert=first, route=transformer._router_form(share_cfg, lp),
            return_counts=True,
        )
        total += np.asarray(y)
        counted += int(counts[first : first + held].sum())
    assert counted == 15 * cfg.moe_top_k     # each assignment on ONE share
    total += np.asarray(only_shared).reshape(total.shape)
    scale = float(np.abs(np.asarray(whole)).max())
    assert np.abs(total.reshape(15, -1) - np.asarray(whole)).max() < 1e-5 * scale
