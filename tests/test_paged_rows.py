"""The paged decode kernel's BLOCK of rows a grid step (interpret mode on
the CPU): a block is a schedule, so its rows come out bit-equal to a row
a step, in every mode of the kernel; which block a batch takes, and that
the trace counts it. The fetch ring's own cases at every number of rows
a step are ``tests/test_pallas_kernels.py::test_paged_decode_fetch_ring``."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops.attention import chunk_attention
from sutro_tpu.ops.pallas_paged import paged_decode_attention

from tests.test_pallas_kernels import (
    LAYER, N_LAYERS, _make_decode_case, _quantize_tokens,
)

# one batch of 16 holds, side by side, a dead slot, one page, powers of
# two, 13 pages (a group of 8, of 4, of 1) and a full table; the rows of
# 0 and 1 pages, of 2 and 0, of 1 and 0 and of 2 and 4 are neighbours
# whose pages the ring of 16 holds beside a free group, so they run
# their digits side by side. Sixteen rows, so that a block of 8 is not
# the whole grid: XLA's CPU backend compiles an interpreted grid of ONE
# step without its loop and rounds a last bit otherwise, whatever the
# rows a step
_B = dict(B=16, NH=4, KVH=2, Dh=16, PS=8, MP=13, NP=224, N_PFX=2)
_B_PAST = [0, 5, 32, 101, 104, 9, 0, 64, 99, 57, 8, 0, 16, 25, 104, 17]
_B_MODES = (
    "plain", "window_buffer", "sink", "sliding_window", "window_start",
    "prefix_carry", "shared", "int8",
)


@functools.lru_cache(maxsize=None)
def _block_case(mode: str):
    """``(call, reference)``: ``call(rows)`` runs the kernel in interpret
    mode at that many rows a grid step over the one batch above."""
    from sutro_tpu.ops.attention import latent_attention
    from sutro_tpu.ops.pallas_paged import prefix_attention_carry

    rng = np.random.default_rng(55)
    B, NH, KVH, Dh, PS, MP, NP, n_pfx = (
        _B[k] for k in ("B", "NH", "KVH", "Dh", "PS", "MP", "NP", "N_PFX")
    )
    shared = mode == "shared"
    if shared:
        KVH, Dh = 1, 128
    KD = KVH * Dh
    f32 = jnp.float32
    q = jnp.asarray(rng.standard_normal((B, 1, NH, Dh)), f32)
    k_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), f32)
    v_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), f32)
    kp = jnp.asarray(rng.standard_normal((N_LAYERS, NP, PS, KD)), f32)
    vp = jnp.asarray(rng.standard_normal((N_LAYERS, NP, PS, KD)), f32)
    table = np.zeros((B, MP), np.int32)
    nxt = 1 + n_pfx
    for b in range(B):
        own = rng.permutation(np.arange(nxt, nxt + MP))
        nxt += MP
        if mode == "prefix_carry":
            # members and non-members of the prefix share every block
            own[:n_pfx] = np.arange(1, 1 + n_pfx)
        table[b] = own
    assert nxt <= NP
    table = jnp.asarray(table)
    past_len = jnp.asarray(_B_PAST, jnp.int32)
    win = jnp.asarray(
        {"sliding_window": 5, "window_start": 21}.get(mode, 0), jnp.int32
    )
    sink = (
        jnp.asarray(rng.standard_normal(NH), f32) if mode == "sink" else None
    )
    kw, ref_kw, win_len = {}, {}, jnp.asarray(0, jnp.int32)
    if mode in ("window_buffer", "shared"):
        win_len = jnp.asarray(3, jnp.int32)
        wk = jnp.asarray(rng.standard_normal((B, 4, KD)), f32)
        wv = jnp.asarray(rng.standard_normal((B, 4, KD)), f32)
        kw = dict(win_k=wk, win_v=None if shared else wv, win_len=win_len)
        ref_kw = dict(win_k=wk, win_v=wv, win_len=win_len)
    if mode == "int8":
        kp, ks = _quantize_tokens(kp)
        vp, vs = _quantize_tokens(vp)
        kw.update(k_scale=ks, v_scale=vs)
        ref_kw.update(past_k_scale=ks, past_v_scale=vs)
    if mode == "window_start":
        kw["window_start"] = True
    if mode == "prefix_carry":
        pfx_len = jnp.where(
            jnp.arange(B) % 2 == 1, 0,
            jnp.where(past_len >= n_pfx * PS, n_pfx * PS, 0),
        )
        m0, l0, acc0 = prefix_attention_carry(
            q[:, 0], kp, vp, LAYER,
            jnp.arange(1, 1 + n_pfx, dtype=jnp.int32), pfx_len,
            past_len, win,
        )
        kw.update(pfx_cnt=pfx_len // PS, m0=m0, l0=l0, acc0=acc0)
    pos = (past_len + win_len)[:, None]
    if shared:
        scale = 24 ** -0.5
        ref = latent_attention(
            q, k_cur[:, :, 0], None, positions=pos,
            valid_len=jnp.ones((B,), jnp.int32), scale=scale, pages=kp,
            layer=LAYER, page_table=table, past_len=past_len,
            value_width=Dh, win_rows=kw["win_k"], win_len=win_len,
        )
        kw["scale"] = scale
    else:
        ref = chunk_attention(
            q, k_cur, v_cur, positions=pos,
            valid_len=jnp.ones((B,), jnp.int32),
            past_k_pages=kp, past_v_pages=vp, layer=LAYER, page_table=table,
            past_len=past_len, window=win, sink=sink, use_pallas=False,
            **ref_kw,
        )

    def call(rows):
        return np.asarray(paged_decode_attention(
            q[:, 0], kp, None if shared else vp, LAYER, table, past_len,
            k_cur[:, 0], None if shared else v_cur[:, 0], win, sink,
            interpret=True, rows=rows, **kw,
        ))

    return call, np.asarray(ref[:, 0])


@pytest.fixture
def block_ring(paged_ring):
    """The ring of the block cases: 16 slots, groups of up to 8 pages."""
    paged_ring(16, 8)


@functools.lru_cache(maxsize=None)
def _one_row_a_step(mode: str):
    """A row a grid step under ``block_ring``, computed once a mode."""
    return _block_case(mode)[0](1)


@pytest.mark.parametrize("rows", [2, 4, 8])
@pytest.mark.parametrize("mode", _B_MODES)
def test_a_block_of_rows_is_the_one_row_schedule_bit_for_bit(
    mode, rows, block_ring
):
    """Rows taken a block a grid step come out BIT-EQUAL to a row a
    step (a row's sums are the same sums in the same order: the block
    is a schedule), and both within the tolerance of the XLA form."""
    call, ref = _block_case(mode)
    one = _one_row_a_step(mode)
    got = call(rows)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, one)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,want", [(7, 1), (6, 2), (12, 4), (64, 8), (1, 1)])
def test_the_rows_a_step_divide_the_batch_and_are_counted(B, want):
    """A batch no larger block divides runs a row a grid step; the
    trace says which it took (``lowering.paged_decode_rows_per_step``)."""
    from sutro_tpu.ops import lowering
    from sutro_tpu.ops.pallas_paged import ring_shape, rows_per_step

    D, GP = ring_shape(8, 32, 4, 6)
    shape = (4, 16, 32, 8, D, GP, 0)
    assert rows_per_step(B, *shape, pool_bytes=4, io_bytes=4) == want
    if B > 12:
        return
    rng = np.random.default_rng(B)
    q, k_cur, v_cur, kp, vp, table, past_len = _make_decode_case(
        rng, B=B, NP=6 * B + 1
    )
    before = lowering.paged_decode_rows_per_step().get(want, 0)
    paged_decode_attention.clear_cache()
    got = paged_decode_attention(
        q[:, 0], kp, vp, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        jnp.asarray(0, jnp.int32), None, interpret=True,
    )
    assert lowering.paged_decode_rows_per_step()[want] == before + 1
    ref = chunk_attention(
        q, k_cur, v_cur, positions=past_len[:, None],
        valid_len=jnp.ones((B,), jnp.int32),
        past_k_pages=kp, past_v_pages=vp, layer=LAYER, page_table=table,
        past_len=past_len, window=jnp.asarray(0, jnp.int32),
        use_pallas=False,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
    )


# (batch, heads, KV heads, head size, pages a sequence, modes, rows a
# grid step) of the benchmark's cells that decode through the kernel
# (perfbench/configs/*.json; a tp=4 shard is what one chip runs)
_CELL_SHAPES = {
    "qwen3-4b.generate-jobs": (64, 32, 8, 128, 16, {}, 8),
    "qwen3-4b.classify-jobs": (64, 32, 8, 128, 16, {"prefix": True}, 8),
    "qwen3-8b-tp4.generate-jobs": (128, 8, 2, 128, 16, {}, 8),
    "mellum2-12b-a2.5b-l8": (64, 32, 4, 128, 64, {}, 8),
    "nemotron-3-nano-30b-a3b-l14": (256, 32, 2, 128, 32, {}, 8),
    # 64 heads over a fused axis of 1,024: a row's float32 accumulator
    # is 256 KB, and eight of them with their blocks pass the limit
    "solar-open2-250b-l8-ep16": (192, 64, 8, 128, 32, {}, 4),
    "joyai-llm-flash-ep16": (32, 32, 1, 640, 64, {"shared": True}, 8),
    "xing4.0-29b-a4b-l7": (128, 32, 1, 640, 64, {"shared": True}, 8),
}


@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_rows_a_step_of_the_cells(cell):
    """The rows a grid step each cell's batch takes (8, but for the
    widest rows), and what the blocks, the scratch and the ring take
    together stays under the scoped VMEM limit (the compiler's own count
    is under this one: ``decode_vmem_bytes``)."""
    from sutro_tpu.ops.pallas_paged import (
        VMEM_LIMIT_BYTES, decode_vmem_bytes, ring_shape, rows_per_step,
    )

    B, NH, KVH, Dh, MP, modes, rows = _CELL_SHAPES[cell]
    KD, PS, W = KVH * Dh, 64, 8
    D, GP = ring_shape(PS, KD, 2, MP)
    shape = (NH, Dh, KD, PS, D, GP, W)
    kw = dict(pool_bytes=2, io_bytes=2, **modes)
    assert rows_per_step(B, *shape, **kw) == rows
    assert decode_vmem_bytes(rows, *shape, **kw) <= VMEM_LIMIT_BYTES
    assert decode_vmem_bytes(1, *shape, **kw) < decode_vmem_bytes(
        rows, *shape, **kw
    )


def test_rows_a_step_stop_at_the_vmem_limit():
    """Rows too wide for eight beside the ring take fewer: a prefix
    carry's float32 accumulators at twice the 4B cell's heads."""
    from sutro_tpu.ops.pallas_paged import ring_shape, rows_per_step

    D, GP = ring_shape(64, 2048, 2, 16)
    shape = (64, 128, 2048, 64, D, GP, 8)
    kw = dict(pool_bytes=2, io_bytes=2, prefix=True)
    assert rows_per_step(64, *shape, **kw) in (1, 2, 4)
