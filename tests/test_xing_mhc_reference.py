"""Xing4.0's layers (``tiny-xing-mhc``: a residual stream of four lanes
mixed a token by manifold-constrained hyper-connections in every
sublayer, round YaRN-scaled latent attention, a dense layer and three
routed ones of 8 experts top-2 beside a shared expert) against the plain
float32 reference (``perfbench/reference/mhc_mla_moe.py``: a token's
4 x 4 matrix through a plain Sinkhorn loop, the mix an einsum, the
latent attention expanded) on seeded random weights.

What is compared is LOGITS, never tokens. Two tolerances, each with its
reason:

- float32 ``TOL`` = 2e-4 of the largest logit: both sides compute in
  float32 and differ in the order of sums, in the Sinkhorn's reciprocal
  a row in place of a division an element, and in the absorbed form's
  algebra; measured about 1e-6. Coefficients computed in bfloat16 under
  the same float32 weights read 1e-2 and more, fifty times over it
  (``test_bfloat16_coefficients_under_a_float32_configuration_fail``).
- bfloat16 ``TOL_BF16`` = 0.06, the benchmark's own
  (``perfbench/reference/tolerance.json``): weights and the four lanes
  rounded to 8 bits of mantissa through eight sublayers, the
  coefficients in float32 from them; measured about 0.02.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import mhc_mla_moe
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models import transformer
from tests.xing_mhc_common import (
    KEYS, MCFG, engine, err, sequence, table_of, want,
)

TOL, TOL_BF16 = 2e-4, 0.06
N_PRE, N_DEC = 45, 6     # across YaRN's original window of 32


def through_the_cache(runner, seq):
    """Prefill ``seq[:N_PRE]`` (one chunk, the EXPANDED form), then
    ``N_DEC`` single steps through the latent cache (ABSORBED), as
    ``perfbench/sut.py`` takes them: logits [1 + N_DEC, V]."""
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
def runner():
    return ModelRunner(MCFG, engine(prefill_chunk=64), num_pages=17)


@pytest.fixture(scope="module")
def readings(runner):
    """The system through the cache in float32 and in bfloat16, each
    against the reference on ITS weights, and the float32 system's own
    logits (the controls' yardstick)."""
    seq = sequence(1, N_PRE + N_DEC)
    positions = range(N_PRE - 1, N_PRE + N_DEC)
    got = through_the_cache(runner, seq)
    out = {"float32": err(got, want(runner, seq, positions)), "got": got,
           "seq": seq, "positions": positions}
    r16 = ModelRunner(
        MCFG, engine(prefill_chunk=64, param_dtype="bfloat16",
                     activation_dtype="bfloat16"), num_pages=17,
    )
    out["bfloat16"] = err(through_the_cache(r16, seq), want(r16, seq, positions))
    return out


def test_float32_prefill_then_decode_through_the_cache_is_the_reference(readings):
    assert readings["float32"] < TOL


def test_bfloat16_is_inside_the_benchmarks_tolerance(readings):
    assert 1e-3 < readings["bfloat16"] < TOL_BF16


def test_bfloat16_coefficients_under_a_float32_configuration_fail(runner):
    """The same float32 weights and stream, the coefficients' projection
    and Sinkhorn in bfloat16: over the float32 tolerance by far."""
    seq = sequence(1, 40)
    ids, pos = jnp.asarray(seq[None]), jnp.arange(40)[None]
    real = transformer.hc_coefficients

    def rounded(cfg, hp, X):
        hp = dict(hp, phi=hp["phi"].astype(jnp.bfloat16))
        lanes = tuple(x.astype(jnp.bfloat16) for x in X)
        return jax.tree.map(
            lambda v: v.astype(jnp.bfloat16).astype(jnp.float32),
            real(cfg, hp, lanes),
        )

    try:
        transformer.hc_coefficients = rounded
        got = transformer.forward(
            MCFG, runner.params, ids, pos, jnp.asarray([40])
        )[0][0]
    finally:
        transformer.hc_coefficients = real
    assert err(np.asarray(got)[20:], want(runner, seq, range(20, 40))) > 10 * TOL


@pytest.mark.parametrize("control", mhc_mla_moe.CONTROLS)
def test_every_mechanism_control_fails_the_tolerance_the_true_form_passes(
    readings, runner, control
):
    """The reference computing ANOTHER model (H_res = I; alpha = 0; one
    Sinkhorn pass; plain rotary frequencies and scale) is far from the
    system: the check can tell each mechanism was computed."""
    other = want(runner, readings["seq"], readings["positions"],
                 controls=(control,))
    assert err(readings["got"], other) > 100 * TOL


# -- the hyper-connection against its formula, token by token --------------------


def _sublayer(seed, n=4, C=24, B=2, T=5, scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    K = n * (n + 2)
    hp = {
        "phi": jax.random.normal(ks[0], (K, n * C)) * (n * C) ** -0.5 * scale,
        "b": jax.random.normal(ks[1], (K,)) * scale,
        "alpha": jnp.asarray([1.0, 0.7, 1.3]),
    }
    X = tuple(jax.random.normal(k, (B, T, C)) for k in jax.random.split(ks[2], n))
    W = jax.random.normal(ks[3], (C, C)) * C ** -0.5
    return hp, X, (lambda u: jnp.tanh(u @ W))


def test_hc_sublayer_is_its_formula_token_by_token():
    hp, X, f = _sublayer(0)
    cfg = dataclasses.replace(MCFG, name="mhc: a sublayer")
    got = jnp.stack(transformer.hc_sublayer(cfg, hp, X, f), axis=2)
    lanes = np.asarray(jnp.stack(X, axis=2), np.float64)        # [B, T, n, C]
    phi, b, a = (np.asarray(hp[k], np.float64) for k in ("phi", "b", "alpha"))
    n = lanes.shape[2]
    for bi in range(lanes.shape[0]):
        for t in range(lanes.shape[1]):
            Xt = lanes[bi, t]
            x = Xt.reshape(-1)
            m = (phi @ x) / math.sqrt(np.mean(x * x) + cfg.norm_eps)
            sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
            pre, post = sig(a[0] * m[:n] + b[:n]), 2 * sig(a[1] * m[n:2 * n] + b[n:2 * n])
            M = np.exp(np.clip(
                a[2] * m[2 * n:].reshape(n, n) + b[2 * n:].reshape(n, n), -30, 30
            ))
            for _ in range(cfg.hc_sinkhorn_iters):
                M = M / (M.sum(0, keepdims=True) + cfg.hc_eps)
                M = M / (M.sum(1, keepdims=True) + cfg.hc_eps)
            u = pre @ Xt
            y = np.asarray(f(jnp.asarray(u[None], jnp.float32))[0], np.float64)
            wanted = M @ Xt + post[:, None] * y[None]
            assert np.abs(np.asarray(got[bi, t]) - wanted).max() < 1e-5


@pytest.mark.parametrize("scale,columns", [(0.25, 1e-5), (1.0, 2e-2), (40.0, None)])
def test_h_res_is_doubly_stochastic_and_finite_at_the_clamps_edges(scale, columns):
    """Rows of the mixing matrix sum to 1 within 1e-5 after twenty passes
    (the last pass is theirs) and so do its columns where the logits are
    mild; logits of unit size leave a column 1e-2 off at a token in ten
    (Sinkhorn converges at the rate the matrix mixes, and the PUBLISHED
    count is twenty: the reference stops there too); with logits far
    past the clamp (+-30) every coefficient stays finite and in its
    range."""
    hp, X, _ = _sublayer(3, scale=scale)
    cfg = dataclasses.replace(MCFG, name="mhc: coefficients")
    pre, post, res = transformer.hc_coefficients(cfg, hp, X)
    M = np.asarray(jnp.stack([jnp.stack(row, -1) for row in res], -2))[..., 0, :, :]
    assert np.isfinite(M).all() and (M >= 0).all()
    for v, top in ((pre, 1.0), (post, 2.0)):
        v = np.asarray(jnp.stack(v))
        assert np.isfinite(v).all() and (v >= 0).all() and (v <= top).all()
    assert np.abs(M.sum(-1) - 1).max() < 1e-5
    if columns is not None:
        assert np.abs(M.sum(-2) - 1).max() < columns


def test_one_lane_is_the_plain_residual_to_the_bit():
    """``hc_mult`` 1: the walk's ``residual`` is ``h + f(h)``, the same
    program as ``tiny-joyai``'s own, whatever the hyper-connection's
    other settings."""
    from sutro_tpu.models.configs import MODEL_CONFIGS

    base = MODEL_CONFIGS["tiny-joyai"]
    other = dataclasses.replace(base, hc_sinkhorn_iters=3, hc_eps=1e-3)
    params = transformer.init_params(base, jax.random.PRNGKey(4), jnp.float32)
    ids = jnp.asarray(sequence(5, 24)[None])
    args = (ids, jnp.arange(24)[None], jnp.asarray([24]))
    a = transformer.forward(base, params, *args)[0]
    b = transformer.forward(other, params, *args)[0]
    assert bool(jnp.all(a == b))
    assert not any("hc_" in k for s in params["layers"].values() for k in s)


# -- YaRN on the latent layer's rotary part ---------------------------------------


def test_yarn_frequencies_and_scale_against_numbers_written_out_by_hand():
    """The published file: rope 64 (32 pairs), theta 10,000, factor 64
    over 4,096, beta 32 / 1. A pair turns ``4096 theta^(-i/32) / 2 pi``
    times in the original window: pair 10 turns 36.7 times (> 32: kept),
    pair 11 27.5 (the ramp starts: low = 10), pair 22 1.16, pair 23 0.87
    (< 1; high = 23). So pairs 0-10 keep theta^(-i/32), pairs 23-31 are
    divided by 64, and pair i between them is scaled by
    ``1 - (i - 10) / 13 (1 - 1/64)``. m(64, 1) = 0.1 ln 64 + 1 =
    1.415888, so cos and sin are times 1.0 and the score's scale is
    2.004740 / sqrt(192)."""
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["xing4.0-29b-a4b-l7"]
    freq, mult = transformer.rope_inv_freq(cfg.rope_theta, 32, cfg, True)
    plain, one = transformer.rope_inv_freq(cfg.rope_theta, 32)
    freq, plain = np.asarray(freq, np.float64), np.asarray(plain, np.float64)
    assert mult == 1.0 and one == 1.0
    np.testing.assert_allclose(plain, 10_000.0 ** (-np.arange(32) / 32), rtol=1e-6)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64, rtol=1e-6)
    for i in (11, 16, 22):
        ramp = (i - 10) / 13
        np.testing.assert_allclose(
            freq[i], plain[i] * (1 - ramp * (1 - 1 / 64)), rtol=1e-5
        )
    assert abs((0.1 * math.log(64) + 1) ** 2 - 2.004740) < 1e-5
    # and the mixer scores with it: a query equal to its only key scores
    # |q|^2 x scale, which the softmax over ONE key cannot show; so the
    # reference's own scale, which the logits tests hold the system to
    d = mhc_mla_moe.dims_of(dict(KEYS, rope_scaling=dict(
        KEYS["rope_scaling"], factor=64, original_max_position_embeddings=4096
    ), qk_rope_head_dim=64))
    np.testing.assert_allclose(
        np.asarray(mhc_mla_moe.yarn_inv_freq(d)), freq, rtol=1e-5
    )
    assert mhc_mla_moe.yarn_mscale(64.0, 1.0) ** 2 == pytest.approx(2.004740, abs=1e-5)


def test_both_pairings_take_their_frequencies_from_one_function():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8))
    pos = jnp.arange(6)[None] * 7
    freq, _ = transformer.rope_inv_freq(10_000.0, 4)
    ang = np.asarray(pos[0, :, None] * freq)                     # [T, 4]
    got = np.asarray(transformer.apply_rope_interleaved(x, pos, 10_000.0))
    a, b = np.asarray(x)[0, :, :, 0::2], np.asarray(x)[0, :, :, 1::2]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(got[0, :, :, 0::2], a * c - b * s, atol=1e-5)
    np.testing.assert_allclose(got[0, :, :, 1::2], b * c + a * s, atol=1e-5)
    half = np.asarray(transformer.apply_rope(x, pos, jnp.float32(10_000.0)))
    a, b = np.asarray(x)[0, :, :, :4], np.asarray(x)[0, :, :, 4:]
    np.testing.assert_allclose(half[0, :, :, :4], a * c - b * s, atol=1e-5)
    np.testing.assert_allclose(half[0, :, :, 4:], b * c + a * s, atol=1e-5)
