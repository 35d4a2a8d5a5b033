"""The kernels at the shapes the Nemotron 3 Nano configuration brings,
in interpret mode on the CPU (the compiles for the chip are in
``tests/perfbench/test_aot_nemotron_h_v5e.py``): flash prefill at 16
query heads a KV head, and the grouped product over an expert whose
width is off the 128-lane grid: the first matrix read output-major
(``transposed``), the second contracting over the width."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops import lowering, moe, pallas_flash, pallas_gmm
from sutro_tpu.ops.attention import chunk_attention


# -- flash prefill at a group of sixteen ------------------------------------------

@pytest.mark.parametrize("window", [0, 70])
def test_flash_prefill_at_sixteen_query_heads_a_kv_head(window):
    rng = np.random.default_rng(3)
    B, T, NH, KVH, Dh = 1, 256, 32, 2, 128
    q = jnp.asarray(rng.standard_normal((B, T, NH, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    assert NH // KVH == 16 == pallas_flash.MAX_GROUP
    assert pallas_flash.flash_prefill_supported(q, k, None, None)
    win = jnp.asarray(window, jnp.int32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    ref = chunk_attention(
        q, k, v, positions=positions, valid_len=jnp.full((B,), T, jnp.int32),
        window=win, sink=None, use_pallas=False,
    )
    got = pallas_flash.flash_prefill(q, k, v, window=win, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_prefill_refuses_a_group_over_sixteen():
    q = jnp.zeros((1, 128, 34, 128), jnp.float32)
    k = jnp.zeros((1, 128, 2, 128), jnp.float32)
    assert not pallas_flash.flash_prefill_supported(q, k, None, None)


def test_the_scratch_at_sixteen_heads_fits_the_kernels_vmem():
    G, BQ, Dh = pallas_flash.MAX_GROUP, pallas_flash.BLOCK_Q, 128
    scratch = (2 * G * BQ * 128 + G * BQ * Dh) * 4
    blocks = 2 * 2 * G * BQ * Dh * 2 + 2 * 2 * pallas_flash.BLOCK_K * Dh * 2
    assert scratch == 3 * 2 ** 20 and scratch + blocks < 8 * 2 ** 20


# -- the grouped product off the lane grid ----------------------------------------

def _sizes(n, total, seed):
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.integers(0, total + 1, n - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [total]]))
    sizes[n // 3] += sizes[n // 2]
    sizes[n // 2] = 0                     # an empty group among them
    return jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("rows,dtype", [
    (96, jnp.float32), (640, jnp.float32), (640, jnp.bfloat16),
    (4608, jnp.float32),
], ids=["under-a-tile", "decode", "decode-bf16", "prefill"])
@pytest.mark.parametrize("layer", [None, 1], ids=["one-layer", "the-stack"])
def test_an_expert_off_the_grid_first_matrix_output_major(rows, dtype, layer):
    """``up`` [G, F, H] with F = 240 = 15 x 16 (as 1,856 = 116 x 16: whole sublane packs, off the
    lanes)
    read where it lies, then ``down`` [G, F, H] contracting over F."""
    E, H, F = 8, 256, 240
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.standard_normal((rows, H)), dtype)
    nan = jnp.nan if layer is not None else 0.0
    up = jnp.full((3 * E, F, H), nan, dtype)
    down = jnp.full((3 * E, F, H), nan, dtype)
    at = (layer or 0) * E
    up = up.at[at : at + E].set(
        jnp.asarray(rng.standard_normal((E, F, H)) * H ** -0.5, dtype))
    down = down.at[at : at + E].set(
        jnp.asarray(rng.standard_normal((E, F, H)) * F ** -0.5, dtype))
    gs = _sizes(E, rows, rows)
    idx = None if layer is None else jnp.int32(layer)
    assert pallas_gmm.grouped_matmul_supported(x, up, transposed=True)
    u = pallas_gmm.grouped_matmul(x, up, gs, idx, transposed=True, interpret=True)
    want_u = jax.lax.ragged_dot(x, jnp.swapaxes(up[at : at + E], 1, 2), gs)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(u, np.float32), np.asarray(want_u, np.float32),
        atol=tol, rtol=tol)
    assert pallas_gmm.grouped_matmul_supported(u, down)
    y = pallas_gmm.grouped_matmul(u, down, gs, idx, interpret=True)
    want_y = jax.lax.ragged_dot(u, down[at : at + E], gs)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want_y, np.float32),
        atol=tol, rtol=tol)
    assert np.isfinite(np.asarray(y, np.float32)).all()


def test_the_gate_at_the_published_widths():
    bf = jnp.bfloat16

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, bf)

    ok = pallas_gmm.grouped_matmul_supported
    # Nemotron 3 Nano: 2,688 x 1,856, the first matrix output-major
    assert ok(s(1536, 2688), s(384, 1856, 2688), transposed=True)
    assert ok(s(1536, 1856), s(384, 1856, 2688))
    # input-major, the width off the grid would be copied whole: refused
    assert not ok(s(1536, 2688), s(384, 2688, 1856))
    # a matrix off the grid and too large to be one block: refused
    assert not ok(s(1536, 8192), s(8, 1856, 8192), transposed=True)
    # off the sublane pack: refused
    assert not ok(s(1536, 2688), s(8, 1860, 2688), transposed=True)
    # the accepted cells' shapes, as before
    assert ok(s(512, 2304), s(512, 2304, 896)) and ok(s(512, 896), s(512, 896, 2304))
    assert ok(s(1024, 2048), s(512, 2048, 1536))
    # and their tiles: a block over the budget with no legal halving
    # takes the small row tile beside it
    assert pallas_gmm._tiles(1536, 2688, 1856, 2) == (128, 128, 1856)
    assert pallas_gmm._tiles(6144, 1856, 2688, 2) == (128, 128, 2688)
    assert pallas_gmm._tiles(16384, 2304, 896, 2) == (512, 128, 896)
    assert pallas_gmm._tiles(512, 2048, 1536, 2) == (128, 128, 1536)


def test_moe_mlp_takes_the_kernel_for_two_matrix_experts(monkeypatch):
    """``use_pallas`` sends both products of a held share of two-matrix
    experts to the kernel (interpreted here), and the result is the
    ``ragged_dot`` path's."""
    real = pallas_gmm.grouped_matmul
    monkeypatch.setattr(
        pallas_gmm, "grouped_matmul",
        lambda *a, **kw: real(*a, **kw, interpret=True),
    )
    H, F, E, held, K = 128, 48, 16, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (4, 8, H))
    router = jax.random.normal(ks[1], (H, E))
    up = jax.random.normal(ks[2], (3, held, F, H)) * H ** -0.5
    down = jax.random.normal(ks[3], (3, held, F, H)) * F ** -0.5
    route = dict(score="sigmoid", select_bias=None, renorm=True, scale=2.5,
                 renorm_eps=1e-20)
    kw = dict(top_k=K, activation="relu2", first_expert=8, route=route,
              layer=jnp.int32(2))
    before = lowering.grouped_matmul_counts()
    got = moe.moe_mlp(x, router, None, up, down, use_pallas=True, **kw)
    after = lowering.grouped_matmul_counts()
    assert after["interpreted"] - before["interpreted"] == 2
    assert after["reference"] == before["reference"]
    want = moe.moe_mlp(x, router, None, up, down, use_pallas=False, **kw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
