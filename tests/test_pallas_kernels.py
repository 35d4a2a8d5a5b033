"""Pallas kernel correctness (interpret mode on CPU).

Each kernel is validated against the pure-jnp reference path in
ops/attention.py — the always-correct fallback — over the shape/flag
matrix the engine actually uses (GQA, sliding windows, sinks, ragged
past lengths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops.attention import chunk_attention
from sutro_tpu.ops.pallas_paged import paged_decode_attention

# every pool here is the stacked [L, NP, PS, KVH*Dh] array the engine
# holds, random in every layer, and every reader is told to read a
# MIDDLE layer: a kernel that ignored its layer index would read layer
# 0's pages and fail the comparison
N_LAYERS = 3
LAYER = jnp.asarray(1, jnp.int32)


def _make_decode_case(
    rng, *, B=3, NH=4, KVH=2, Dh=16, PS=8, MP=6, NP=32, past=None
):
    q = jnp.asarray(rng.standard_normal((B, 1, NH, Dh)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    # pools carry the fused [L, NP, PS, KVH*Dh] layout (engine/kvcache.py)
    k_pages = jnp.asarray(
        rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
    )
    # distinct pages per row
    table = np.zeros((B, MP), np.int32)
    next_p = 1
    for b in range(B):
        table[b] = np.arange(next_p, next_p + MP)
        next_p += MP
    if past is None:
        past = rng.integers(1, MP * PS, B)
    past_len = jnp.asarray(past, jnp.int32)
    return q, k_cur, v_cur, k_pages, v_pages, jnp.asarray(table), past_len


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("with_sink", [False, True])
def test_paged_decode_matches_reference(window, with_sink):
    rng = np.random.default_rng(42)
    NH = 4
    q, k_cur, v_cur, kp, vp, table, past_len = _make_decode_case(rng)
    sink = (
        jnp.asarray(rng.standard_normal(NH), jnp.float32)
        if with_sink
        else None
    )
    win = jnp.asarray(window, jnp.int32)
    B = q.shape[0]
    positions = past_len[:, None]

    ref = chunk_attention(
        q, k_cur, v_cur,
        positions=positions,
        valid_len=jnp.ones((B,), jnp.int32),
        past_k_pages=kp, past_v_pages=vp, layer=LAYER, page_table=table,
        past_len=past_len, window=win, sink=sink,
        use_pallas=False,
    )
    got = paged_decode_attention(
        q[:, 0], kp, vp, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        win, sink, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
    )


def test_paged_decode_zero_past():
    """First decode step after an empty prefill: only self-attention."""
    rng = np.random.default_rng(0)
    q, k_cur, v_cur, kp, vp, table, _ = _make_decode_case(rng)
    past_len = jnp.zeros((q.shape[0],), jnp.int32)
    got = paged_decode_attention(
        q[:, 0], kp, vp, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        jnp.asarray(0, jnp.int32), None, interpret=True,
    )
    # softmax over a single key == that key's value
    want = jnp.repeat(v_cur[:, 0], q.shape[2] // k_cur.shape[2], axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5
    )


def test_decode_step_via_runner_matches_dense(tiny_ecfg):
    """End-to-end: the runner's paged decode (jnp path after refactor)
    still reproduces full-context forward logits."""
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["tiny-dense"]
    runner = ModelRunner(cfg, tiny_ecfg)
    rng = np.random.default_rng(1)
    n = 11
    prompt = rng.integers(0, 200, n).astype(np.int32)
    table = np.zeros((tiny_ecfg.max_pages_per_seq,), np.int32)
    table[:4] = [1, 2, 3, 4]
    logits = runner.prefill(prompt, table)

    nxt = int(np.argmax(logits))
    B = tiny_ecfg.decode_batch_size
    tables = np.zeros((B, tiny_ecfg.max_pages_per_seq), np.int32)
    tables[0] = table
    last = np.zeros((B,), np.int32)
    last[0] = nxt
    past = np.zeros((B,), np.int32)
    past[0] = n
    toks, _ = runner.decode_step(
        last, past, tables, jax.random.PRNGKey(0),
        np.zeros((B,), np.float32),  # greedy
        np.ones((B,), np.float32),
    )

    # dense reference over prompt + nxt
    full = np.concatenate([prompt, [nxt]]).astype(np.int32)
    ids = jnp.asarray(full[None])
    pos = jnp.arange(len(full), dtype=jnp.int32)[None]
    vlen = jnp.asarray([len(full)], jnp.int32)
    ref_logits, _, _ = transformer.forward(
        cfg, runner.params, ids, pos, vlen
    )
    ref_tok = int(np.argmax(np.asarray(ref_logits[0, -1])))
    assert int(toks[0]) == ref_tok


# ---------------------------------------------------------------------------
# flash prefill kernel
# ---------------------------------------------------------------------------

from sutro_tpu.ops.pallas_flash import (  # noqa: E402
    flash_prefill,
    flash_prefill_supported,
)


def _make_prefill_case(rng, *, B=2, T=128, NH=4, KVH=2, Dh=128):
    q = jnp.asarray(rng.standard_normal((B, T, NH, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window", [0, 5, 200])
@pytest.mark.parametrize("with_sink", [False, True])
def test_flash_prefill_matches_reference(window, with_sink):
    rng = np.random.default_rng(7)
    B, T, NH = 2, 256, 4
    q, k, v = _make_prefill_case(rng, B=B, T=T, NH=NH)
    sink = (
        jnp.asarray(rng.standard_normal(NH), jnp.float32)
        if with_sink
        else None
    )
    win = jnp.asarray(window, jnp.int32)
    positions = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None], (B, T)
    )
    valid_len = jnp.full((B,), T, jnp.int32)

    ref = chunk_attention(
        q, k, v, positions=positions, valid_len=valid_len,
        window=win, sink=sink, use_pallas=False,
    )
    got = flash_prefill(q, k, v, window=win, sink=sink, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_prefill_ragged_valid_len():
    """Padded rows: every used position (t < valid_len) must match the
    jnp path, which additionally masks padded keys — causality makes the
    two equivalent on used rows."""
    rng = np.random.default_rng(11)
    B, T = 3, 128
    q, k, v = _make_prefill_case(rng, B=B, T=T)
    valid = jnp.asarray([128, 57, 1], jnp.int32)
    positions = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None], (B, T)
    )
    ref = chunk_attention(
        q, k, v, positions=positions, valid_len=valid,
        window=None, sink=None, use_pallas=False,
    )
    got = flash_prefill(q, k, v, interpret=True)
    for b in range(B):
        n = int(valid[b])
        np.testing.assert_allclose(
            np.asarray(got)[b, :n],
            np.asarray(ref)[b, :n],
            atol=2e-5,
            rtol=2e-5,
        )


def test_flash_prefill_gate():
    rng = np.random.default_rng(0)
    q, k, v = _make_prefill_case(rng, B=1, T=128)
    assert flash_prefill_supported(q, k, None, None)
    q2, k2, _ = _make_prefill_case(rng, B=1, T=64)  # sub-block chunk
    assert not flash_prefill_supported(q2, k2, None, None)
    q3 = jnp.zeros((1, 128, 4, 64), jnp.float32)  # Dh % 128 != 0
    k3 = jnp.zeros((1, 128, 2, 64), jnp.float32)
    assert not flash_prefill_supported(q3, k3, None, None)


@pytest.mark.parametrize("window", [0, 5])
def test_paged_decode_with_window_buffer(window):
    """Fused-window variant: pages + window buffer + current token must
    reproduce the jnp reference fed the same window K/V."""
    rng = np.random.default_rng(21)
    NH, KVH, Dh, W = 4, 2, 16, 8
    q, k_cur, v_cur, kp, vp, table, past_len = _make_decode_case(rng)
    B = q.shape[0]
    # window buffers carry the fused [B, W, KVH*Dh] layout
    win_k = jnp.asarray(
        rng.standard_normal((B, W, KVH * Dh)), jnp.float32
    )
    win_v = jnp.asarray(
        rng.standard_normal((B, W, KVH * Dh)), jnp.float32
    )
    win_len = jnp.asarray(5, jnp.int32)  # slots 0..4 valid
    win = jnp.asarray(window, jnp.int32)
    positions = (past_len + win_len)[:, None]

    ref = chunk_attention(
        q, k_cur, v_cur,
        positions=positions,
        valid_len=jnp.ones((B,), jnp.int32),
        past_k_pages=kp, past_v_pages=vp, layer=LAYER, page_table=table,
        past_len=past_len, window=win, sink=None,
        use_pallas=False,
        win_k=win_k, win_v=win_v, win_len=win_len,
    )
    got = paged_decode_attention(
        q[:, 0], kp, vp, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        win, None, win_k=win_k, win_v=win_v, win_len=win_len,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# grouped matmul (MoE expert GEMM)
# ---------------------------------------------------------------------------

from sutro_tpu.ops import pallas_gmm  # noqa: E402
from sutro_tpu.ops.pallas_gmm import grouped_matmul  # noqa: E402


def _decode_sizes():
    """64 groups of 0-40 rows, several empty, and a run of four tiny
    groups inside one tile of 128 rows so that it spans five."""
    sizes = np.random.default_rng(5).integers(1, 41, 64)
    sizes[[3, 4, 5, 17, 40, 63]] = 0
    sizes[:10] = [90, 2, 0, 0, 0, 0, 3, 1, 2, 120]
    sizes[62] += -sizes.sum() % 8
    return [int(n) for n in sizes]


_GMM_CASES = {
    # the four cases the padded layout was held to
    "ragged-one-empty": dict(sizes=[100, 28, 0, 128]),
    "tile-aligned": dict(sizes=[64, 64, 64, 64]),
    "single-hot-expert": dict(sizes=[256, 0, 0, 0]),
    "tiny-groups": dict(sizes=[1, 2, 3, 250]),
    # a decode step's: a tile spans five groups, groups span tiles
    "decode-shaped": dict(sizes=_decode_sizes()),
    "decode-shaped-bf16": dict(sizes=_decode_sizes(), dtype=jnp.bfloat16),
    # tiles of 512 rows in sub-tiles of 128; the smallest group is 3 rows
    # and one sub-tile holds rows of no visit's group but its own
    "prefill-shaped": dict(sizes=[0, 3, 1000, 5, 0, 2000, 1088, 0]),
    "prefill-shaped-bf16": dict(
        sizes=[700, 9, 1300, 0, 2087], dtype=jnp.bfloat16
    ),
    # the last tile overhangs the rows; fewer rows than a tile
    "rows-off-the-tile": dict(sizes=[100, 0, 60, 40]),
    "fewer-rows-than-a-tile": dict(sizes=[0, 5, 0, 11]),
    # the flat stack of three layers, read at the middle one, every
    # other layer's experts NaN: indexed, not scanned
    "stack-middle-layer": dict(sizes=_decode_sizes(), layers=3, layer=1),
    "stack-last-layer-prefill": dict(
        sizes=[0, 3, 1000, 5, 0, 2000, 1088, 0], layers=2, layer=1
    ),
}


def _gmm_case(sizes, dtype=jnp.float32, layers=None, layer=None, seed=13):
    rng = np.random.default_rng(seed)
    E, H, F = len(sizes), 128, 256
    lhs = jnp.asarray(rng.standard_normal((sum(sizes), H)), dtype)
    rhs = jnp.asarray(rng.standard_normal((E, H, F)), dtype)
    stack, at = rhs, None
    if layers:
        stack = jnp.full((layers * E, H, F), jnp.nan, dtype)
        stack = stack.at[layer * E:(layer + 1) * E].set(rhs)
        at = jnp.int32(layer)
    return lhs, rhs, stack, jnp.asarray(sizes, jnp.int32), at


@pytest.mark.parametrize("case", sorted(_GMM_CASES))
def test_grouped_matmul_matches_ragged_dot(case):
    lhs, rhs, stack, gs, layer = _gmm_case(**_GMM_CASES[case])
    want = np.asarray(jax.lax.ragged_dot(lhs, rhs, gs), np.float32)
    got = np.asarray(
        grouped_matmul(lhs, stack, gs, layer, interpret=True), np.float32
    )
    assert np.isfinite(got).all()
    tol = 2e-4 if lhs.dtype == jnp.float32 else 2e-2 * np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=tol, rtol=2e-4)


@pytest.mark.parametrize("rows", [40, 640])
def test_grouped_matmul_reads_the_expert_a_row_is_sent_to(rows):
    """All rows to each expert in turn, that expert's product alone
    against a plain matmul: one wrong expert among many is below the
    sight of a rule over whole logits (PERF.md section 7 row 18)."""
    rng = np.random.default_rng(3)
    E, H, F = 6, 128, 128
    lhs = jnp.asarray(rng.standard_normal((rows, H)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2 * E, H, F)), jnp.float32)
    for layer in (None, 1):
        for e in range(E):
            gs = jnp.zeros((E,), jnp.int32).at[e].set(rows)
            got = grouped_matmul(
                lhs, rhs, gs, None if layer is None else jnp.int32(layer),
                interpret=True,
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(lhs @ rhs[(layer or 0) * E + e]),
                atol=2e-4, rtol=2e-4, err_msg=f"layer {layer} expert {e}",
            )


def test_grouped_matmul_visits_each_touched_group_once_at_decode_sizes():
    """The grid's metadata: at a decode step's sizes every group with
    rows is visited once a tile it reaches (its expert fetched once when
    it lies in one tile), empty groups never, in row order."""
    sizes = _decode_sizes()
    M, tm = sum(sizes), 128
    offsets, group, tile, count = (
        np.asarray(a) for a in pallas_gmm._visits(
            jnp.asarray(sizes, jnp.int32), M, tm
        )
    )
    n = int(count[0])
    ends = np.cumsum(sizes)
    want = [
        (g, t) for g, size in enumerate(sizes) if size
        for t in range((ends[g] - size) // tm, (ends[g] - 1) // tm + 1)
    ]
    assert list(zip(group[:n], tile[:n])) == want
    assert len(group) == -(-M // tm) + len(sizes) - 1 >= n
    # what is left of the static grid repeats the last visit: no fetch
    assert (group[n:] == group[n - 1]).all() and (tile[n:] == tile[n - 1]).all()
    assert list(offsets) == [0] + list(ends)
    assert pallas_gmm._tiles(512, 2304, 896, 2) == (128, 128, 896)
    assert pallas_gmm._tiles(16384, 2304, 896, 2) == (512, 128, 896)
    # an expert's block over the VMEM budget is split by columns
    assert pallas_gmm._tiles(256, 4096, 1536, 2) == (128, 128, 768)


# ---------------------------------------------------------------------------
# the paged decode kernel's fetch ring
# ---------------------------------------------------------------------------

_R = dict(B=8, NH=4, KVH=2, Dh=16, PS=8, MP=6, NP=64, N_PFX=2)
# one batch holds every length that matters to a page walk: nothing, one
# token, a page less one, a page, a page and one, the whole table, and
# an EMPTY row between two full ones (the ring hands over across a row
# that fetches nothing)
_R_PAST = [0, 1, 7, 8, 9, 48, 0, 48]


def _ring_tables(layout: str, rng) -> np.ndarray:
    """``run``: one ascending run a row, as the allocator's first fit
    gives; ``scattered``: the same pages in any order; ``shared``: every
    row's table starts with the same N_PFX pages (a job's shared prefix)
    and goes on with its own run."""
    B, MP, n_pfx = _R["B"], _R["MP"], _R["N_PFX"]
    table = np.zeros((B, MP), np.int32)
    nxt = 1 + n_pfx
    for b in range(B):
        own = MP - n_pfx if layout == "shared" else MP
        run = np.arange(nxt, nxt + own)
        nxt += own
        if layout == "scattered":
            rng.shuffle(run)
        if layout == "shared":
            table[b, :n_pfx] = np.arange(1, 1 + n_pfx)
        table[b, MP - own:] = run
    assert nxt <= _R["NP"]
    return table


def _quantize_tokens(x):
    """int8 values + per-token scales, as write_kv stores them."""
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0
    q = jnp.round(x / jnp.maximum(scale, 1e-12)[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


_RING_MODES = ("plain", "window", "sink", "window_buffer", "int8")
_RING_CASES = [
    # (layout, mode, ring pages, pages a group)
    *[(lay, m, 4, 2) for lay in ("run", "scattered", "shared")
      for m in _RING_MODES],
    # the carry of a shared prefix: those rows' fetches start past it
    ("shared", "prefix_carry", 4, 2),
    ("shared", "prefix_carry", 2, 1),
    # other rings: two slots of one page; groups of up to four (a table
    # of six pages is a group of four and one of two) in a ring of two
    # of them; and one that never wraps, as real pages get
    ("run", "plain", 2, 1),
    ("scattered", "window_buffer", 8, 4),
    ("run", "int8", 8, 4),
    ("shared", "plain", 32, 4),
]


@pytest.mark.parametrize("rows", [8, 4, 2, 1])
@pytest.mark.parametrize(
    "layout,mode,ring_pages,group_pages", _RING_CASES,
    ids=[f"{a}-{m}-ring{d}x{g}" for a, m, d, g in _RING_CASES],
)
def test_paged_decode_fetch_ring(
    layout, mode, ring_pages, group_pages, rows, paged_ring
):
    """The kernel's one fetch schedule against the jnp reference: a ring
    of page fetches over the batch's (row, page) sequence, whatever the
    table's layout, with rows of every length in one batch, at every
    number of rows a grid step (8 is what the batch of 8 gets)."""
    from sutro_tpu.ops.pallas_paged import prefix_attention_carry

    paged_ring(ring_pages, group_pages)
    rng = np.random.default_rng(31)
    B, NH, KVH, Dh, PS, MP, NP, n_pfx = (
        _R[k] for k in ("B", "NH", "KVH", "Dh", "PS", "MP", "NP", "N_PFX")
    )
    q = jnp.asarray(rng.standard_normal((B, 1, NH, Dh)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    pool = (N_LAYERS, NP, PS, KVH * Dh)
    kp = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    table = jnp.asarray(_ring_tables(layout, rng))
    past_len = jnp.asarray(_R_PAST, jnp.int32)
    win = jnp.asarray(5 if mode == "window" else 0, jnp.int32)
    sink = (
        jnp.asarray(rng.standard_normal(NH), jnp.float32)
        if mode == "sink" else None
    )
    ks = vs = None
    if mode == "int8":
        kp, ks = _quantize_tokens(kp)
        vp, vs = _quantize_tokens(vp)
    wkw, win_len = {}, jnp.asarray(0, jnp.int32)
    if mode == "window_buffer":
        win_len = jnp.asarray(3, jnp.int32)
        wkw = dict(
            win_k=jnp.asarray(
                rng.standard_normal((B, 4, KVH * Dh)), jnp.float32
            ),
            win_v=jnp.asarray(
                rng.standard_normal((B, 4, KVH * Dh)), jnp.float32
            ),
            win_len=win_len,
        )
    carry = {}
    if mode == "prefix_carry":
        # rows that hold the whole prefix take its carry; a row shorter
        # than the prefix walks what it has itself
        pfx_len = jnp.where(past_len >= n_pfx * PS, n_pfx * PS, 0)
        m0, l0, acc0 = prefix_attention_carry(
            q[:, 0], kp, vp, LAYER,
            jnp.arange(1, 1 + n_pfx, dtype=jnp.int32), pfx_len,
            past_len, win,
        )
        carry = dict(pfx_cnt=pfx_len // PS, m0=m0, l0=l0, acc0=acc0)

    ref = chunk_attention(
        q, k_cur, v_cur,
        positions=(past_len + win_len)[:, None],
        valid_len=jnp.ones((B,), jnp.int32),
        past_k_pages=kp, past_v_pages=vp, layer=LAYER,
        past_k_scale=ks, past_v_scale=vs, page_table=table,
        past_len=past_len, window=win, sink=sink,
        use_pallas=False, **wkw,
    )
    got = paged_decode_attention(
        q[:, 0], kp, vp, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        win, sink, interpret=True, k_scale=ks, v_scale=vs, rows=rows,
        **wkw, **carry,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
    )


def test_ring_shape_of_the_cells():
    """The ring the benchmark's cells get: 16 slots of 128 KB pages on
    one chip (4 MiB of K and V), 32 of a tp=4 shard's 32 KB pages; up to
    eight pages of 64 tokens a group, a power of two; never under two
    of the largest group."""
    from sutro_tpu.ops.pallas_paged import ring_shape

    assert ring_shape(64, 1024, 2, 16) == (16, 8)
    assert ring_shape(64, 256, 2, 16) == (32, 8)
    assert ring_shape(128, 2048, 2, 8) == (8, 4)
    assert ring_shape(256, 4096, 2, 4) == (4, 2)
    assert ring_shape(8, 32, 4, 6) == (32, 4)


# ---------------------------------------------------------------------------
# KV page write kernel (RMW + roll)
# ---------------------------------------------------------------------------

from sutro_tpu.engine.kvcache import KVCache, write_kv  # noqa: E402
from sutro_tpu.ops.pallas_kv import kv_write_pallas  # noqa: E402


@pytest.mark.parametrize(
    "starts,valids,tb",
    [
        ([0, 8, 3], [16, 16, 5], 16),    # aligned, offset, ragged
        ([7, 60, 0], [16, 9, 0], 16),    # page-crossing, empty row
        ([0, 5, 63], [40, 33, 1], 40),   # multi-page runs
    ],
)
def test_kv_write_pallas_matches_scatter(starts, valids, tb):
    """The RMW+roll write kernel (interpret mode) must land exactly the
    same bytes as the XLA scatter fallback, at any offset/page split,
    and leave every untouched row intact."""
    rng = np.random.default_rng(5)
    L, NP, PS, KD = 2, 12, 8, 256
    B, MP = 3, 4
    k0 = jnp.asarray(rng.standard_normal((L, NP, PS, KD)), jnp.float32)
    v0 = jnp.asarray(rng.standard_normal((L, NP, PS, KD)), jnp.float32)
    table = np.zeros((B, MP), np.int32)
    nxt = 1
    for b in range(B):
        table[b] = np.arange(nxt, nxt + MP)
        nxt += MP
    kc = jnp.asarray(rng.standard_normal((L, B, tb, KD)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((L, B, tb, KD)), jnp.float32)
    start = jnp.asarray(starts, jnp.int32)
    valid = jnp.asarray(valids, jnp.int32)
    tab = jnp.asarray(table)

    ref = write_kv(
        KVCache(k_pages=k0, v_pages=v0), kc, vc, tab, start, valid,
        use_pallas=False,
    )
    got_k, got_v = kv_write_pallas(
        k0.copy(), v0.copy(), kc, vc, tab, start, valid, interpret=True
    )
    # page 0 is the garbage page: the scatter fallback dumps invalid
    # tokens there, the kernel skips them — its content is unspecified
    np.testing.assert_array_equal(
        np.asarray(got_k)[:, 1:], np.asarray(ref.k_pages)[:, 1:]
    )
    np.testing.assert_array_equal(
        np.asarray(got_v)[:, 1:], np.asarray(ref.v_pages)[:, 1:]
    )


@pytest.mark.parametrize("window", [0, 7])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode_prefix_carry_injection(window, quantized):
    """Shared-prefix (Hydragen-style) mode: computing the table-head
    prefix's attention ONCE outside the kernel (prefix_attention_carry)
    and injecting it as the online-softmax carry while the kernel skips
    those pages must match the plain kernel walking the full table —
    including rows OUTSIDE the prefix group (pfx_cnt 0, cold carry) and
    under sliding windows that cut into or past the prefix."""
    from sutro_tpu.ops.pallas_paged import prefix_attention_carry

    rng = np.random.default_rng(7)
    B, NH, KVH, Dh, PS, MP, NP = 4, 4, 2, 16, 8, 6, 40
    n_pfx = 3  # 24 prefix tokens
    q = jnp.asarray(rng.standard_normal((B, NH, Dh)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, KVH, Dh)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, KVH, Dh)), jnp.float32)
    if quantized:
        k_pages = jnp.asarray(
            rng.integers(-127, 127, (N_LAYERS, NP, PS, KVH * Dh)), jnp.int8
        )
        v_pages = jnp.asarray(
            rng.integers(-127, 127, (N_LAYERS, NP, PS, KVH * Dh)), jnp.int8
        )
        k_scale = jnp.asarray(
            rng.uniform(0.005, 0.02, (N_LAYERS, NP, PS)), jnp.float32
        )
        v_scale = jnp.asarray(
            rng.uniform(0.005, 0.02, (N_LAYERS, NP, PS)), jnp.float32
        )
    else:
        k_pages = jnp.asarray(
            rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
        )
        v_pages = jnp.asarray(
            rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
        )
        k_scale = v_scale = None
    # rows 0..2 share prefix pages [1, 2, 3]; row 3 is NOT in the group
    pfx_pages = np.array([1, 2, 3], np.int32)
    table = np.zeros((B, MP), np.int32)
    next_p = 4
    for b in range(B):
        if b < 3:
            table[b, :n_pfx] = pfx_pages
            own = np.arange(next_p, next_p + (MP - n_pfx))
            table[b, n_pfx:] = own
            next_p += MP - n_pfx
        else:
            table[b] = np.arange(next_p, next_p + MP)
            next_p += MP
    # member rows: past spans prefix + some own tokens; non-member: own
    past = np.array(
        [n_pfx * PS + 5, n_pfx * PS + 11, n_pfx * PS + 2, 17], np.int32
    )
    table = jnp.asarray(table)
    past_len = jnp.asarray(past)
    win = jnp.asarray(window, jnp.int32)

    ref = paged_decode_attention(
        q, k_pages, v_pages, LAYER, table, past_len, k_cur, v_cur, win, None,
        interpret=True,
        k_scale=k_scale, v_scale=v_scale,
    )

    pfx_len = jnp.asarray(
        [n_pfx * PS, n_pfx * PS, n_pfx * PS, 0], jnp.int32
    )
    pfx_cnt = jnp.asarray([n_pfx, n_pfx, n_pfx, 0], jnp.int32)
    m0, l0, acc0 = prefix_attention_carry(
        q, k_pages, v_pages, LAYER, jnp.asarray(pfx_pages), pfx_len,
        past_len,  # q_pos: no window buffer, query sits at past_len
        win, k_scale=k_scale, v_scale=v_scale,
    )
    got = paged_decode_attention(
        q, k_pages, v_pages, LAYER, table, past_len, k_cur, v_cur, win, None,
        interpret=True,
        k_scale=k_scale, v_scale=v_scale,
        pfx_cnt=pfx_cnt, m0=m0, l0=l0, acc0=acc0,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [0, 7, 2])
def test_prefix_carry_pallas_matches_xla_gather(window):
    """In-place prefix-carry kernel (page-indexed BlockSpecs reading the
    shared pages straight from the pool) vs the XLA gather reference —
    same (m, l, acc) carry, including windows that cut into the prefix
    and rows outside the group (pfx_len 0). window=2 masks the WHOLE
    prefix for every row: both paths must agree on the all-masked carry
    (l == 0, acc == 0)."""
    from sutro_tpu.ops.pallas_paged import (
        prefix_attention_carry,
        prefix_attention_carry_pallas,
    )

    rng = np.random.default_rng(11)
    B, NH, KVH, Dh, PS, NP = 4, 4, 2, 16, 8, 40
    n_pfx = 3
    q = jnp.asarray(rng.standard_normal((B, NH, Dh)), jnp.float32)
    k_pages = jnp.asarray(
        rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
    )
    pfx_pages = jnp.asarray([1, 2, 3], jnp.int32)
    pfx_len = jnp.asarray(
        [n_pfx * PS, n_pfx * PS, n_pfx * PS, 0], jnp.int32
    )
    q_pos = jnp.asarray([29, 35, 26, 17], jnp.int32)
    win = jnp.asarray(window, jnp.int32)

    m_ref, l_ref, a_ref = prefix_attention_carry(
        q, k_pages, v_pages, LAYER, pfx_pages, pfx_len, q_pos, win
    )
    m_got, l_got, a_got = prefix_attention_carry_pallas(
        q, k_pages, v_pages, LAYER, pfx_pages, pfx_len, q_pos, win,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(l_got), np.asarray(l_ref), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(a_got), np.asarray(a_ref), rtol=2e-5, atol=2e-5
    )
    # m only matters where something was in range (l > 0); all-masked
    # rows carry an arbitrary -inf-ish max in both implementations
    live = np.asarray(l_ref) > 0
    np.testing.assert_allclose(
        np.asarray(m_got)[live], np.asarray(m_ref)[live],
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("window", [0, 7])
def test_paged_decode_with_pallas_carry_injection(window):
    """End-to-end: the in-place kernel's carry injected into the paged
    decode kernel must match the plain kernel walking the full table —
    the exact composition ops/attention.py runs on the split-prefix
    decode path when prefix_carry_supported holds."""
    from sutro_tpu.ops.pallas_paged import prefix_attention_carry_pallas

    rng = np.random.default_rng(13)
    B, NH, KVH, Dh, PS, MP, NP = 4, 4, 2, 16, 8, 6, 40
    n_pfx = 3
    q = jnp.asarray(rng.standard_normal((B, NH, Dh)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, KVH, Dh)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, KVH, Dh)), jnp.float32)
    k_pages = jnp.asarray(
        rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((N_LAYERS, NP, PS, KVH * Dh)), jnp.float32
    )
    pfx_pages = np.array([1, 2, 3], np.int32)
    table = np.zeros((B, MP), np.int32)
    next_p = 4
    for b in range(B):
        if b < 3:
            table[b, :n_pfx] = pfx_pages
            table[b, n_pfx:] = np.arange(
                next_p, next_p + (MP - n_pfx)
            )
            next_p += MP - n_pfx
        else:
            table[b] = np.arange(next_p, next_p + MP)
            next_p += MP
    past = np.array(
        [n_pfx * PS + 5, n_pfx * PS + 11, n_pfx * PS + 2, 17], np.int32
    )
    table = jnp.asarray(table)
    past_len = jnp.asarray(past)
    win = jnp.asarray(window, jnp.int32)

    ref = paged_decode_attention(
        q, k_pages, v_pages, LAYER, table, past_len, k_cur, v_cur, win, None,
        interpret=True,
    )
    pfx_len = jnp.asarray(
        [n_pfx * PS, n_pfx * PS, n_pfx * PS, 0], jnp.int32
    )
    pfx_cnt = jnp.asarray([n_pfx, n_pfx, n_pfx, 0], jnp.int32)
    m0, l0, acc0 = prefix_attention_carry_pallas(
        q, k_pages, v_pages, LAYER, jnp.asarray(pfx_pages), pfx_len,
        past_len, win, interpret=True,
    )
    got = paged_decode_attention(
        q, k_pages, v_pages, LAYER, table, past_len, k_cur, v_cur, win, None,
        interpret=True,
        pfx_cnt=pfx_cnt, m0=m0, l0=l0, acc0=acc0,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_prefix_carry_supported_flags():
    """Shape gate for the in-place kernel: lane-aligned fused KV dim,
    sublane-aligned page size, float pool only (int8 KV rides the XLA
    gather fallback)."""
    from sutro_tpu.ops.pallas_paged import prefix_carry_supported

    q = jnp.zeros((2, 4, 128), jnp.float32)          # Dh lane-aligned
    good = jnp.zeros((2, 8, 8, 256), jnp.float32)
    assert prefix_carry_supported(q, good)
    assert not prefix_carry_supported(
        jnp.zeros((2, 4, 16), jnp.float32),          # Dh = 16
        jnp.zeros((2, 8, 8, 32), jnp.float32),
    )
    assert not prefix_carry_supported(
        q, jnp.zeros((2, 8, 6, 256), jnp.float32)    # PS % 8 != 0
    )
    assert not prefix_carry_supported(
        q, good, k_scale=jnp.zeros((2, 8, 8), jnp.float32)
    )


def test_the_grouped_product_is_counted_beside_the_kernels_not_among_them():
    """``lowering.grouped_matmul_counts()`` says which grouped product a
    process traced. It is no key of ``snapshot()``: the benchmark's
    numbers check and chip_smoke.py hold every ``use_pallas`` engine to
    ``lowered > 0`` for every key there, and a dense model runs no routed
    layer. ``device_report`` shows it."""
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import device_report
    from sutro_tpu.ops import lowering

    snap, before = lowering.snapshot(), lowering.grouped_matmul_counts()
    assert set(before) == {"lowered", "interpreted", "reference"}
    lhs = jnp.ones((24, 128), jnp.float32)       # a shape no test traces
    rhs = jnp.ones((3, 128, 384), jnp.float32)
    gs = jnp.asarray([8, 0, 16], jnp.int32)
    grouped_matmul(lhs, rhs, gs, interpret=True)
    jax.make_jaxpr(grouped_matmul)(lhs, rhs, gs)   # traced for Mosaic
    after = lowering.grouped_matmul_counts()
    assert after == {
        "lowered": before["lowered"] + 1,
        "interpreted": before["interpreted"] + 1,
        "reference": before["reference"],
    }
    assert lowering.snapshot() == snap
    assert set(snap) == {"paged_decode", "flash_prefill", "kv_write"}
    report = device_report(EngineConfig(use_pallas=False))
    assert report["grouped_matmul"] == after
    assert report["kernel_paths"] == snap


def test_lowering_counters_tell_the_three_paths_apart():
    """ops/lowering.py is how a chip run proves which path it built:
    an interpret-mode kernel call is counted but NOT as lowered for the
    TPU, and a ``use_pallas=True`` call that a shape gate sends to the
    jnp reference is counted as such (chip_smoke.py asserts on these)."""
    from sutro_tpu.ops import lowering
    from sutro_tpu.ops.attention import chunk_attention

    # shapes no other test traces, so the jitted wrapper's body runs
    B, T, NH, KVH, Dh = 3, 128, 2, 1, 128
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, T, NH, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    before = lowering.snapshot()["flash_prefill"]
    flash_prefill(q, k, k, interpret=True)
    mid = lowering.snapshot()["flash_prefill"]
    assert mid["interpreted"] == before["interpreted"] + 1
    assert mid["lowered"] == before["lowered"]
    assert mid["reference"] == before["reference"]

    # T=24 fails the flash gate (T % 128): use_pallas=True runs the jnp
    # reference, and says so
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32)[None], (B, 24))
    out = chunk_attention(
        q[:, :24], k[:, :24], k[:, :24], positions=pos,
        valid_len=jnp.full((B,), 24, jnp.int32), use_pallas=True,
    )
    assert out.shape == (B, 24, NH, Dh)
    after = lowering.snapshot()["flash_prefill"]
    assert after["reference"] == mid["reference"] + 1
    assert after["lowered"] == mid["lowered"]
