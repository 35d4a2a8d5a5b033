"""Pallas TPU kernel: paged-KV decode attention.

The decode hot loop (SURVEY §7.3 "Paged-KV attention in Pallas"). For each
decode step the jnp fallback gathers a contiguous ``[B, CTX, KVH, Dh]``
view of the page pool per layer — a pure HBM copy that dominates decode
time. This kernel reads K/V pages **in place** with flash-style online
softmax across pages.

Design (second generation — the first used grid ``(B, MP)`` with one
BlockSpec-fetched page per grid step, which cost a block DMA for every
table slot, used or not, and ~µs of grid overhead per tiny block; at
28 layers x B=64 x MP=8 that grid tax dominated the whole decode step):

- grid ``(B,)``: one grid step per decode row;
- the page walk lives INSIDE the kernel as a ``fori_loop`` bounded by the
  row's ACTUAL page count (``ceil(past_len/PS)``) — unused table slots
  cost nothing;
- the operand is the WHOLE stacked pool ``[L, NP, PS, KVH*Dh]``
  (``memory_space=ANY``, HBM-resident) and the layer is a scalar-prefetch
  index: pages are fetched as ``pool.at[layer, page]`` with
  double-buffered ``make_async_copy`` (the DMA for page ``i+1`` overlaps
  compute on page ``i``). No caller slices a layer out of the stack: XLA
  cannot fuse a slice into a custom call's operand and would copy the
  layer's pool (76 MB at 579 pages) before every call;
- KV heads are processed by a static in-kernel loop, one ``[G, PS]``
  score tile per head, accumulating ``(m, l, acc)`` in VMEM scratch;
- the current token's K/V, the optional multi-step decode window buffer
  (tokens sampled in the current fused window, not yet written to the
  pool — see engine/runner.decode_multi), and the optional gpt-oss
  attention sink all join the softmax in the finalization step;
- the layer index and per-layer sliding windows (Gemma3 / gpt-oss) are
  dynamic operands, so one compiled kernel serves every layer of the
  ``lax.scan``.

All math is float32.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lowering

NEG_INF = -1e30


def _paged_decode_kernel(
    # scalar prefetch: page_table [B*MP], past_len [B], window [1],
    # layer [1] (which layer of the stacked pool this call reads), then
    # — in shared-prefix (Hydragen-style) mode — pfx_pages_cnt [B], and
    # — when the caller carries a decode window buffer — win_len [1]
    *refs,
    max_pages_per_seq: int,
    page_size: int,
    scale: float,
    kvh: int,
    window_slots: int = 0,
    chunk_pages: int = 1,
    cross_row: bool = False,
    quantized: bool = False,
    prefix: bool = False,
):
    # ref layout varies with (window_slots, quantized, prefix) — walk an
    # index instead of a per-case tuple unpack
    it = iter(refs)
    page_table_ref = next(it)
    past_len_ref = next(it)
    window_ref = next(it)
    layer_ref = next(it)
    pfx_cnt_ref = next(it) if prefix else None
    win_len_ref = next(it) if window_slots else None
    q_ref = next(it)
    k_pool_ref = next(it)
    v_pool_ref = next(it)
    ks_pool_ref = next(it) if quantized else None
    vs_pool_ref = next(it) if quantized else None
    k_cur_ref = next(it)
    v_cur_ref = next(it)
    wk_ref = next(it) if window_slots else None
    wv_ref = next(it) if window_slots else None
    m0_ref = next(it) if prefix else None
    l0_ref = next(it) if prefix else None
    acc0_ref = next(it) if prefix else None
    sink_ref = next(it)
    out_ref = next(it)
    kbuf = next(it)
    vbuf = next(it)
    ksem = next(it)
    vsem = next(it)
    ksbuf = next(it) if quantized else None
    vsbuf = next(it) if quantized else None
    kssem = next(it) if quantized else None
    vssem = next(it) if quantized else None
    m_ref = next(it)
    l_ref = next(it)
    acc_ref = next(it)

    b = pl.program_id(0)
    MP = max_pages_per_seq
    PS = page_size
    CH = chunk_pages
    CT = CH * PS  # tokens per fetched chunk
    NH = q_ref.shape[1]
    Dh = q_ref.shape[2]
    G = NH // kvh
    KD = kvh * Dh

    past = past_len_ref[b]
    nchunks = (past + CT - 1) // CT
    # current token's global position: tokens already in pages plus any
    # fused-window tokens not yet written back
    pos = past + (win_len_ref[0] if window_slots else 0)
    win = window_ref[0]
    # the pools are the whole [L, NP, PS, KD] stacks, resident in HBM:
    # every DMA below indexes [layer, page] itself
    layer = layer_ref[0]

    # Block-diagonal queries: fold the per-KV-head loop into ONE score
    # matmul and ONE value matmul per chunk. Row i (= head i, KV head
    # i // G) of q_bd carries q[i] in column block i // G of the fused
    # [KVH*Dh] axis and zeros elsewhere, so q_bd @ k_chunk.T computes
    # every head's scores in a single MXU op (the off-block FLOPs are
    # wasted but free — the kernel is bound by op count / latency, not
    # MXU throughput: 2*KVH tiny per-head dots per chunk cost ~3x more
    # wall time than these two). Mosaic cannot merge (KVH, Dh) into the
    # lane dim in-kernel, so the page pool arrives pre-fused [.., KD]
    # and lane-space masks are built from iota instead of reshapes.
    q = q_ref[0].astype(jnp.float32)                      # [NH, Dh]
    row_head = jax.lax.broadcasted_iota(jnp.int32, (NH, KD), 0) // G
    col_head = jax.lax.broadcasted_iota(jnp.int32, (NH, KD), 1) // Dh
    blk_kd = (row_head == col_head).astype(jnp.float32)   # [NH, KD]
    q_rep = jnp.concatenate([q] * kvh, axis=1)            # [NH, KD]
    q_bd = q_rep * blk_kd
    # selector S[kd, d] = (kd % Dh == d): one dot extracts each row's
    # own head block from fused-lane space back to [NH, Dh]
    sel_kd = jax.lax.broadcasted_iota(jnp.int32, (KD, Dh), 0)
    sel_d = jax.lax.broadcasted_iota(jnp.int32, (KD, Dh), 1)
    S = (sel_kd % Dh == sel_d).astype(jnp.float32)        # [KD, Dh]

    # Shared-prefix (Hydragen-style) mode: the first pfx_cnt pages of
    # this row's table hold a prefix whose K/V is SHARED with other
    # rows. Their attention was computed ONCE for the whole batch
    # outside the kernel (prefix_attention_carry — the pages are read
    # from HBM once instead of once per row) and arrives as the initial
    # online-softmax carry; the page walk below starts AFTER them.
    # Non-member rows carry (m=-inf, l=0, acc=0) — exactly the cold
    # init — and start at page 0. Online softmax is associative, so the
    # result is bit-comparable to walking the prefix pages in-row.
    if prefix:
        m_ref[...] = jnp.broadcast_to(
            m0_ref[0][:, None].astype(jnp.float32), m_ref.shape
        )
        l_ref[...] = jnp.broadcast_to(
            l0_ref[0][:, None].astype(jnp.float32), l_ref.shape
        )
        acc_ref[...] = acc0_ref[0].astype(jnp.float32)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # CH == 1: each chunk is one table-walked page (any layout).
    # CH > 1: the row's pages are one ascending run (contiguous-first
    # allocator) — chunk i is pages [start + i*CH, start + (i+1)*CH),
    # ONE DMA for CH pages instead of CH DMAs. The caller guarantees
    # CH-1 slack pages at the pool end so the final chunk's over-read
    # stays in bounds (over-read tokens are masked by ``tok < past``).
    #
    # cross_row: row b also starts row b+1's FIRST chunk after its own
    # page walk drains (all kbuf/vbuf reads done), so the next grid
    # step's warmup latency hides behind this row's finalize + the grid
    # transition. Slots are row-parity offset (chunk i of row r lives in
    # slot (r+i)%2) so the handed-over chunk lands where the next row's
    # walk expects it and never collides with a buffer still being read.
    # Requires "arbitrary" grid semantics (cross-step scratch flow).

    def _slot(row, i):
        return jax.lax.rem(row + i, 2) if cross_row else jax.lax.rem(i, 2)

    def k_dma(row, i, slot):
        if CH == 1:  # per-page walk: any table layout
            return pltpu.make_async_copy(
                k_pool_ref.at[layer, page_table_ref[row * MP + i]],
                kbuf.at[slot, 0],
                ksem.at[slot],
            )
        return pltpu.make_async_copy(
            k_pool_ref.at[
                layer, pl.ds(page_table_ref[row * MP] + i * CH, CH)
            ],
            kbuf.at[slot],
            ksem.at[slot],
        )

    def v_dma(row, i, slot):
        if CH == 1:
            return pltpu.make_async_copy(
                v_pool_ref.at[layer, page_table_ref[row * MP + i]],
                vbuf.at[slot, 0],
                vsem.at[slot],
            )
        return pltpu.make_async_copy(
            v_pool_ref.at[
                layer, pl.ds(page_table_ref[row * MP] + i * CH, CH)
            ],
            vbuf.at[slot],
            vsem.at[slot],
        )

    def _scale_dmas(row, i, slot):
        # int8 KV: the per-token dequant scales ride their own (tiny)
        # DMAs — pools arrive pre-shaped [L, NP, 1, PS] so the fetched
        # chunk lands lane-major [CH, 1, PS] and each page's scale row
        # is a legal [1, PS] broadcast against a score slice (merging
        # sublanes into lanes in-kernel is unsupported)
        if CH == 1:
            return (
                pltpu.make_async_copy(
                    ks_pool_ref.at[layer, page_table_ref[row * MP + i]],
                    ksbuf.at[slot, 0],
                    kssem.at[slot],
                ),
                pltpu.make_async_copy(
                    vs_pool_ref.at[layer, page_table_ref[row * MP + i]],
                    vsbuf.at[slot, 0],
                    vssem.at[slot],
                ),
            )
        start = page_table_ref[row * MP] + i * CH
        return (
            pltpu.make_async_copy(
                ks_pool_ref.at[layer, pl.ds(start, CH)],
                ksbuf.at[slot],
                kssem.at[slot],
            ),
            pltpu.make_async_copy(
                vs_pool_ref.at[layer, pl.ds(start, CH)],
                vsbuf.at[slot],
                vssem.at[slot],
            ),
        )

    def _start_chunk(row, i, slot):
        k_dma(row, i, slot).start()
        v_dma(row, i, slot).start()
        if quantized:
            for dma in _scale_dmas(row, i, slot):
                dma.start()

    def _chunks_of(row):
        return (past_len_ref[row] + CT - 1) // CT

    # shared-prefix mode: skip the prefix pages (their carry was
    # injected above). Requires CH == 1 and no cross_row (wrapper
    # enforces both), so chunk index == page index.
    i0 = pfx_cnt_ref[b] if prefix else 0

    # warmup: row 0 fetches its own first chunk; under cross_row every
    # later row's first chunk was started by its predecessor
    self_warm = (b == 0) if cross_row else (nchunks > i0)

    @pl.when(jnp.logical_and(self_warm, nchunks > i0))
    def _warmup():
        _start_chunk(b, i0, _slot(b, i0))

    def page_step(i, _):
        slot = _slot(b, i)
        nxt = _slot(b, i + 1)

        @pl.when(i + 1 < nchunks)
        def _prefetch_next():
            _start_chunk(b, i + 1, nxt)

        k_dma(b, i, slot).wait()
        v_dma(b, i, slot).wait()
        if quantized:
            for dma in _scale_dmas(b, i, slot):
                dma.wait()

        chunk_start = i * CT
        tok = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (NH, CT), 1
        )
        ok = tok < past
        # windowless (win <= 0) ORed in instead of a boolean select —
        # Mosaic cannot legalize arith.select on i1 vectors
        ok = jnp.logical_and(
            ok, jnp.logical_or(pos - tok < win, win <= 0)
        )
        # [CH, PS, KD] -> [CT, KD]: leading-dim collapse only (the lane
        # dim KD is untouched — Mosaic supports this shape cast)
        k = kbuf[slot].reshape(CT, KD).astype(jnp.float32)
        v = vbuf[slot].reshape(CT, KD).astype(jnp.float32)
        s = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [NH, CT]
        if quantized:
            # K dequant folds into the scores: q.(k_int*ks) = (q.k_int)*ks
            # — one [1, PS] lane-broadcast multiply per page of the
            # chunk (CH is static), lane-concatenated back to [NH, CT]
            s = jnp.concatenate(
                [
                    s[:, pg * PS : (pg + 1) * PS] * ksbuf[slot, pg]
                    for pg in range(CH)
                ],
                axis=1,
            )
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, 0]                             # [NH]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)                  # [NH]
        p = jnp.exp(s - m_new[:, None])                  # [NH, CT]
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
        if quantized:
            # V dequant folds into the probabilities for the value dot
            # ONLY — the normalizer l above sums the true p:
            # p.(v_int*vs) = (p*vs).v_int
            pv = jnp.concatenate(
                [
                    p[:, pg * PS : (pg + 1) * PS] * vsbuf[slot, pg]
                    for pg in range(CH)
                ],
                axis=1,
            )
        else:
            pv = p
        # acc holds the full [NH, KVH*Dh] product; only each row's own
        # head block is meaningful (extracted at the end)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        return 0

    jax.lax.fori_loop(i0, nchunks, page_step, 0)

    if cross_row:
        # hand off: start the NEXT row's first chunk now that every DMA
        # of this row has been waited (both slots idle). The matching
        # wait is the next grid step's page_step(0) on slot (b+1)%2 —
        # predicated on the same ``nchunks > 0`` so semaphores balance.
        nb = b + 1
        # clamp the probe: logical_and evaluates both operands, so the
        # last row must not read past_len_ref[B] (OOB SMEM on hardware)
        nb_c = jnp.minimum(nb, pl.num_programs(0) - 1)

        @pl.when(jnp.logical_and(nb < pl.num_programs(0), _chunks_of(nb_c) > 0))
        def _handoff():
            _start_chunk(nb, 0, _slot(nb, 0))

    # finalize: fused-window tokens + current token + attention sink,
    # in the same block-diagonal space (2 dots total, not 2 per head)
    W = window_slots
    k_cur = k_cur_ref[0].astype(jnp.float32)             # [1, KD]
    v_cur = v_cur_ref[0].astype(jnp.float32)             # [1, KD]
    sink = sink_ref[0].astype(jnp.float32)               # [NH]

    s_self = jax.lax.dot_general(
        q_bd, k_cur, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0] * scale                                      # [NH]
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.maximum(s_self, sink))
    if W:
        # window tokens: slot s holds the fused window's s-th sampled
        # token at position past+s; the query is at pos
        wlen = win_len_ref[0]
        wk = wk_ref[0].astype(jnp.float32)               # [W, KD]
        wv = wv_ref[0].astype(jnp.float32)
        slot_i = jax.lax.broadcasted_iota(jnp.int32, (NH, W), 1)
        ok_w = slot_i < wlen
        ok_w = jnp.logical_and(
            ok_w,
            jnp.logical_or(wlen - slot_i < win, win <= 0),
        )
        s_w = jax.lax.dot_general(
            q_bd, wk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [NH, W]
        s_w = jnp.where(ok_w, s_w, NEG_INF)
        m_new = jnp.maximum(m_new, jnp.max(s_w, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p_self = jnp.exp(s_self - m_new)
    p_sink = jnp.exp(sink - m_new)
    l = l_ref[:, 0] * alpha + p_self + p_sink
    acc = acc_ref[...] * alpha[:, None] + p_self[:, None] * v_cur
    if W:
        p_w = jnp.exp(s_w - m_new[:, None])              # [NH, W]
        l = l + jnp.sum(p_w, axis=1)
        acc = acc + jax.lax.dot_general(
            p_w, wv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    # extract each row's own head block from the block-diagonal acc:
    # zero the off-blocks, then sum the lane blocks with the selector dot
    acc_bd = jax.lax.dot_general(
        acc * blk_kd, S, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [NH, Dh]
    out = acc_bd / jnp.maximum(l, 1e-30)[:, None]
    out_ref[0] = out.astype(out_ref.dtype)


def prefix_attention_carry(
    q: jax.Array,            # [B, NH, Dh] current-step queries
    k_pages: jax.Array,      # [L, NP, PS, KVH*Dh] the stacked page pool
    v_pages: jax.Array,
    layer: jax.Array,        # scalar int32 — the layer to read
    pfx_pages: jax.Array,    # [Pp] int32 — the SHARED prefix's pages
    pfx_len: jax.Array,      # [B] int32 — prefix tokens per row (0 for
    #                          rows outside the prefix group)
    q_pos: jax.Array,        # [B] int32 — each query's global position
    window: jax.Array,       # scalar int32; 0 => full attention
    k_scale: Optional[jax.Array] = None,  # [L, NP, PS] int8-KV scales
    v_scale: Optional[jax.Array] = None,
):
    """Online-softmax carry ``(m0, l0, acc0)`` of attention over a
    job-shared page-aligned prefix, computed ONCE for the whole batch
    (Hydragen / cascade-inference decomposition: the prefix K/V is the
    same physical pages for every member row, so one [Pp] gather on
    ``[layer, pfx_pages]`` of the stack reads them from HBM once per
    layer per step instead of once per row inside the paged kernel's
    per-row walk).

    Returned in the paged kernel's spaces for direct carry injection
    (``paged_decode_attention(..., pfx_cnt, m0, l0, acc0)``): m0/l0
    ``[B, NH]`` f32, acc0 ``[B, NH, KVH*Dh]`` f32 block-diagonal (each
    query row's accumulator sits in its own KV head's lane block).
    Rows with ``pfx_len == 0`` get the cold carry (-inf, 0, 0) — inside
    the kernel they are indistinguishable from non-prefix rows.
    Softmax-associativity makes the final attention equal to walking
    the prefix pages in-row (same f32 math, different summation order).
    """
    B, NH, Dh = q.shape
    _, NP, PS, KD = k_pages.shape
    KVH = KD // Dh
    G = NH // KVH
    scale = Dh ** -0.5
    Pp = pfx_pages.shape[0]
    Lp = Pp * PS

    kp = k_pages[layer, pfx_pages].astype(jnp.float32)  # [Pp, PS, KD]
    vp = v_pages[layer, pfx_pages].astype(jnp.float32)
    if k_scale is not None:
        kp = kp * k_scale[layer, pfx_pages][..., None].astype(jnp.float32)
        vp = vp * v_scale[layer, pfx_pages][..., None].astype(jnp.float32)
    kp = kp.reshape(Lp, KVH, Dh)
    vp = vp.reshape(Lp, KVH, Dh)

    qg = q.reshape(B, KVH, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,lkd->bkgl", qg, kp) * scale  # [B, KVH, G, Lp]
    t = jnp.arange(Lp, dtype=jnp.int32)
    ok = t[None, :] < pfx_len[:, None]                # [B, Lp]
    win = jnp.asarray(window, jnp.int32)
    ok = jnp.logical_and(
        ok,
        jnp.logical_or(
            (q_pos[:, None] - t[None, :]) < win, win <= 0
        ),
    )
    okb = ok[:, None, None, :]
    s = jnp.where(okb, s, NEG_INF)
    m = jnp.max(s, axis=-1)                           # [B, KVH, G]
    # p computed under the mask, NOT as exp(s - m): an all-masked row
    # has m = -inf and exp(-inf - -inf) would be 1, not 0
    p = jnp.where(okb, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgl,lkd->bkgd", p, vp)         # [B, KVH, G, Dh]

    m0 = m.reshape(B, NH)
    l0 = l.reshape(B, NH)
    # block-diagonal fused space: query row i's accumulator goes into
    # lane block i // G
    head = jnp.arange(NH, dtype=jnp.int32) // G       # [NH]
    onehot = jax.nn.one_hot(head, KVH, dtype=jnp.float32)  # [NH, KVH]
    acc0 = jnp.einsum(
        "bnd,nk->bnkd", acc.reshape(B, NH, Dh), onehot
    ).reshape(B, NH, KD)
    return m0, l0, acc0


def _prefix_carry_kernel(
    # scalar prefetch: pfx_pages [Pp] int32 and layer [1] int32 (they
    # drive the K/V index maps)
    pages_ref,
    layer_ref,
    q_bd_ref,      # [B*NH, KD] f32 block-diagonal queries (resident)
    k_page_ref,    # [1, 1, PS, KD] — THE prefix page for this grid
    #                step, fetched in place from the stacked HBM pool by
    #                the (layer, page)-indexed BlockSpec index map (no
    #                gather, no per-layer slice)
    v_page_ref,
    ok_ref,        # [1, B, PS] f32 0/1 — combined len+window mask
    m_out_ref,     # [B*NH, 128] f32 (lane-broadcast; caller takes [:,0])
    l_out_ref,
    acc_out_ref,   # [B*NH, KD] f32 block-diagonal accumulator
    m_ref, l_ref, acc_ref,  # VMEM scratch carries across grid steps
    *, scale: float, n_heads: int,
):
    del pages_ref, layer_ref  # read by the index maps only
    p = pl.program_id(0)
    BNH, KD = acc_ref.shape
    PS = k_page_ref.shape[2]
    B = BNH // n_heads

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_bd = q_bd_ref[...].astype(jnp.float32)            # [BNH, KD]
    k = k_page_ref[0, 0].astype(jnp.float32)            # [PS, KD]
    v = v_page_ref[0, 0].astype(jnp.float32)
    # [B, PS] row mask -> every head of row b shares it: sublane
    # broadcast then leading-dim collapse (the only reshape Mosaic
    # supports — the lane dim PS is untouched)
    ok = jnp.broadcast_to(
        ok_ref[0][:, None, :], (B, n_heads, PS)
    ).reshape(BNH, PS) > 0.0
    s = jax.lax.dot_general(
        q_bd, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                           # [BNH, PS]
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    # p under the mask, NOT bare exp(s - m): an all-masked step keeps
    # m_new = -inf and exp(-inf - -inf) would contribute 1, not 0
    pr = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
    l_ref[...] = jnp.broadcast_to(
        (l_ref[:, 0] * alpha + jnp.sum(pr, axis=1))[:, None],
        l_ref.shape,
    )
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        pr, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

    @pl.when(p == pl.num_programs(0) - 1)
    def _writeback():
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]
        acc_out_ref[...] = acc_ref[...]


def prefix_carry_supported(
    q: jax.Array, k_pages: jax.Array,
    k_scale: Optional[jax.Array] = None,
) -> bool:
    """Gate for the in-place Pallas prefix-carry kernel. int8-KV rides
    the XLA-gather fallback (the dequant-scale plumbing isn't worth a
    second kernel variant for a cache whose pages are read once per
    step either way)."""
    Dh = q.shape[-1]
    PS = k_pages.shape[2]
    return Dh % 128 == 0 and PS % 8 == 0 and k_scale is None


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefix_attention_carry_pallas(
    q: jax.Array,            # [B, NH, Dh]
    k_pages: jax.Array,      # [L, NP, PS, KVH*Dh]
    v_pages: jax.Array,
    layer: jax.Array,        # scalar int32
    pfx_pages: jax.Array,    # [Pp] int32
    pfx_len: jax.Array,      # [B] int32
    q_pos: jax.Array,        # [B] int32
    window: jax.Array,       # scalar int32; 0 => full attention
    *,
    interpret: bool = False,
):
    """``prefix_attention_carry`` with the shared pages read IN PLACE:
    grid ``(Pp,)`` over the prefix's pages, each step's K/V block
    fetched straight out of the stacked HBM page pool by a BlockSpec
    index map on ``(layer[0], pages[p])`` — the [Pp, PS, KD] gather
    copy the XLA path materializes per layer per step never exists.
    Sequential grid; the online-softmax carry lives in VMEM scratch and
    writes back on the last page. Bit-comparable to the XLA path: same
    f32 math in the same per-page order."""
    B, NH, Dh = q.shape
    _, NP, PS, KD = k_pages.shape
    KVH = KD // Dh
    G = NH // KVH
    scale = Dh ** -0.5
    Pp = pfx_pages.shape[0]
    Lp = Pp * PS

    # block-diagonal fused queries (XLA side — reshapes are free here):
    # row b*NH+n carries q[b, n] in lane block n // G, zeros elsewhere
    row_head = jax.lax.broadcasted_iota(jnp.int32, (NH, KD), 0) // G
    col_head = jax.lax.broadcasted_iota(jnp.int32, (NH, KD), 1) // Dh
    blk = (row_head == col_head).astype(jnp.float32)     # [NH, KD]
    q_rep = jnp.concatenate([q.astype(jnp.float32)] * KVH, axis=-1)
    q_bd = (q_rep * blk[None]).reshape(B * NH, KD)

    # combined length+window mask, page-major [Pp, B, PS] so each grid
    # step loads its page's [B, PS] slab
    t = jnp.arange(Lp, dtype=jnp.int32)
    ok = t[None, :] < pfx_len[:, None]                   # [B, Lp]
    win = jnp.asarray(window, jnp.int32)
    ok = jnp.logical_and(
        ok,
        jnp.logical_or((q_pos[:, None] - t[None, :]) < win, win <= 0),
    )
    ok_pg = (
        ok.astype(jnp.float32).reshape(B, Pp, PS).swapaxes(0, 1)
    )

    page_spec = pl.BlockSpec(
        # THE in-place read: this step's block is HBM page pages[p] of
        # layer lyr[0] of the stack, DMA'd by the pipeline itself
        (1, 1, PS, KD), lambda p, pages, lyr: (lyr[0], pages[p], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Pp,),
        in_specs=[
            pl.BlockSpec((B * NH, KD), lambda p, *s: (0, 0)),
            page_spec,
            page_spec,
            pl.BlockSpec((1, B, PS), lambda p, *s: (p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B * NH, 128), lambda p, *s: (0, 0)),
            pl.BlockSpec((B * NH, 128), lambda p, *s: (0, 0)),
            pl.BlockSpec((B * NH, KD), lambda p, *s: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((B * NH, 128), jnp.float32),
            pltpu.VMEM((B * NH, 128), jnp.float32),
            pltpu.VMEM((B * NH, KD), jnp.float32),
        ],
    )
    m_o, l_o, acc_o = pl.pallas_call(
        functools.partial(
            _prefix_carry_kernel, scale=scale, n_heads=NH
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * NH, 128), jnp.float32),
            jax.ShapeDtypeStruct((B * NH, 128), jnp.float32),
            jax.ShapeDtypeStruct((B * NH, KD), jnp.float32),
        ],
        # the carry threads scratch state page to page: sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        pfx_pages.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_bd, k_pages, v_pages, ok_pg,
    )
    m0 = m_o[:, 0].reshape(B, NH)
    l0 = l_o[:, 0].reshape(B, NH)
    # the kernel's value matmul fills every lane; only each row's own
    # head block is meaningful — zero the off-blocks (XLA side) so the
    # carry is exactly the XLA path's block-diagonal acc0 and group
    # sums stay garbage-free
    acc0 = acc_o.reshape(B, NH, KD) * blk[None]
    return m0, l0, acc0


# Below this table capacity (tokens) the XLA gather fallback wins on
# grid/DMA overhead. With the in-kernel page walk the kernel's work is
# proportional to ACTUAL context, so it wins essentially everywhere —
# the gate is kept env-overridable for benchmarking the crossover.
PALLAS_PAGED_MIN_CTX = int(
    os.environ.get("SUTRO_PAGED_MIN_CTX", "0")
)

# Cross-row DMA warmup: each row starts the next row's first chunk as
# soon as its own page walk drains, hiding per-row first-fetch latency
# behind finalize + grid transition. Costs "arbitrary" grid semantics
# (rows run sequentially on one core) — free on single-TensorCore chips
# (v5e); on megacore parts (v4/v5p) "parallel" row-splitting may win
# instead. Default OFF until chip-validated (interpret mode cannot model
# DMA/semaphore timing): SUTRO_KV_XROW=1 enables.
PALLAS_PAGED_XROW = os.environ.get("SUTRO_KV_XROW", "0") == "1"


def chunk_pages_for(
    page_size: int,
    max_pages_per_seq: int,
    kv_heads: int = 8,
    head_dim: int = 128,
    dtype_bytes: int = 2,
    budget_bytes: int = 1 << 20,
) -> int:
    """Pages fetched per DMA in contiguous-KV mode: the largest divisor
    of MP whose chunk stays under ``budget_bytes`` PER double-buffer
    slot (4 buffers total: K+V x 2 slots — 1 MiB each keeps the scratch
    well inside ~16 MiB VMEM alongside m/l/acc). Callers enabling
    chunked fetch must (a) allocate slots as contiguous page runs and
    (b) leave ``chunk-1`` unallocatable slack pages at the pool end for
    the final chunk's masked over-read (engine/runner)."""
    page_bytes = max(page_size * kv_heads * head_dim * dtype_bytes, 1)
    budget = max(1, budget_bytes // page_bytes)
    ch = min(max_pages_per_seq, budget)
    while ch > 1 and max_pages_per_seq % ch:
        ch -= 1
    return max(ch, 1)


def paged_decode_supported(
    q: jax.Array, k_pages: jax.Array, page_table: jax.Array
) -> bool:
    """Shape/size gate for the compiled TPU path (interpret mode has no
    such constraints — tests call paged_decode_attention(interpret=True))."""
    Dh = q.shape[-1]
    PS = k_pages.shape[2]
    ctx_capacity = page_table.shape[1] * PS
    return (
        Dh % 128 == 0 and PS % 8 == 0
        and ctx_capacity >= PALLAS_PAGED_MIN_CTX
    )


@functools.partial(
    jax.jit,
    static_argnames=("kv_chunk", "interpret", "cross_row"),
)
def paged_decode_attention(
    q: jax.Array,          # [B, NH, Dh] — current-step queries
    k_pages: jax.Array,    # [L, NP, PS, KVH*Dh] — the stacked FUSED pool
    v_pages: jax.Array,
    layer: jax.Array,      # scalar int32 — the layer this call reads
    page_table: jax.Array, # [B, MP] int32
    past_len: jax.Array,   # [B] int32 — tokens already in the cache
    k_cur: jax.Array,      # [B, KVH, Dh] — current token K (post-RoPE)
    v_cur: jax.Array,
    window: jax.Array,     # scalar int32; 0 => full attention
    sink: Optional[jax.Array] = None,   # [NH] logits or None
    win_k: Optional[jax.Array] = None,  # [B, W, KVH*Dh] fused-window K
    win_v: Optional[jax.Array] = None,
    win_len: Optional[jax.Array] = None,  # scalar int32 — valid slots
    *,
    kv_chunk: int = 1,  # pages per DMA (>1 requires contiguous runs)
    interpret: bool = False,
    cross_row: Optional[bool] = None,  # None => PALLAS_PAGED_XROW
    # int8 KV mode: pages are int8 and these carry the per-token
    # dequant scales [L, NP, PS] f32 (engine/kvcache.py)
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    # shared-prefix (Hydragen-style) mode: rows whose table head holds a
    # job-shared prefix skip those pages (pfx_cnt[b] of them) and start
    # from the injected online-softmax carry (prefix_attention_carry) —
    # the shared pages are then read from HBM once per step for the
    # whole batch instead of once per row. Forces kv_chunk=1, no
    # cross_row.
    pfx_cnt: Optional[jax.Array] = None,   # [B] int32 pages to skip
    m0: Optional[jax.Array] = None,        # [B, NH] f32
    l0: Optional[jax.Array] = None,        # [B, NH] f32
    acc0: Optional[jax.Array] = None,      # [B, NH, KVH*Dh] f32 (block-diag)
) -> jax.Array:
    """Returns [B, NH, Dh] attention outputs for one decode step.

    The page pools are the WHOLE stacked ``[L, NP, PS, KVH*Dh]``
    arrays (engine/kvcache.py) and stay in HBM; ``layer`` rides the
    scalar prefetch and every page DMA indexes ``[layer, page]``. A
    per-layer slice as the operand would be materialized by XLA before
    the custom call (76 MB a layer at the 4B cell's pool, K and V, every
    layer of every step). The kernel's block-diagonal matmuls contract
    over the fused trailing axis. The small per-step tensors (k_cur,
    win_k, sink) are reshaped into the fused layout HERE, outside the
    kernel, where XLA reshapes are free.

    ``win_k/win_v/win_len`` carry the multi-step decode window buffer
    (engine/runner decode_multi): tokens sampled earlier in the fused
    window whose K/V have NOT been written to the page pool yet — the
    bulk page write happens once per window, outside the step scan, so
    the multi-GB pool is never copied per step."""
    lowering.record_kernel("paged_decode", interpret=interpret)
    B, NH, Dh = q.shape
    L, NP, PS, KD = k_pages.shape
    KVH = k_cur.shape[1]
    MP = page_table.shape[1]
    scale = Dh ** -0.5
    W = 0 if win_k is None else win_k.shape[1]

    if sink is None:
        sink_g = jnp.full((1, NH), NEG_INF, jnp.float32)
    else:
        sink_g = sink.astype(jnp.float32).reshape(1, NH)

    if cross_row is None:
        cross_row = PALLAS_PAGED_XROW
    quantized = k_scale is not None
    prefix = pfx_cnt is not None
    if prefix:
        # carry injection needs chunk index == page index, and the
        # cross-row handoff fetches the next row's chunk 0 which a
        # prefix row would skip
        assert kv_chunk == 1, "shared-prefix mode requires kv_chunk=1"
        cross_row = False
    kernel = functools.partial(
        _paged_decode_kernel,
        max_pages_per_seq=MP,
        page_size=PS,
        scale=scale,
        kvh=KVH,
        window_slots=W,
        chunk_pages=kv_chunk,
        cross_row=cross_row,
        quantized=quantized,
        prefix=prefix,
    )

    # index maps take *s so the scalar-prefetch arity (4 to 6) needs
    # no per-case lambdas
    in_specs = [
        pl.BlockSpec((1, NH, Dh), lambda b, *s: (b, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # V pool stays in HBM
    ]
    scalars = [
        page_table.reshape(-1).astype(jnp.int32),
        past_len.astype(jnp.int32),
        jnp.asarray(window, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
    ]
    if prefix:
        scalars.append(pfx_cnt.astype(jnp.int32))
    operands = [
        q,
        k_pages,
        v_pages,
    ]
    if quantized:
        # pre-shaped [L, NP, 1, PS] (a bitcast of the stack): the
        # kernel's scale chunks land lane-major (see _scale_dmas)
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        operands += [
            k_scale.astype(jnp.float32).reshape(L, NP, 1, PS),
            v_scale.astype(jnp.float32).reshape(L, NP, 1, PS),
        ]
    in_specs += [
        pl.BlockSpec((1, 1, KD), lambda b, *s: (b, 0, 0)),
        pl.BlockSpec((1, 1, KD), lambda b, *s: (b, 0, 0)),
    ]
    operands += [
        k_cur.reshape(B, 1, KD),
        v_cur.reshape(B, 1, KD),
    ]
    if W:
        scalars.append(jnp.asarray(win_len, jnp.int32).reshape(1))
        in_specs += [
            pl.BlockSpec((1, W, KD), lambda b, *s: (b, 0, 0)),
            pl.BlockSpec((1, W, KD), lambda b, *s: (b, 0, 0)),
        ]
        operands += [win_k, win_v]
    if prefix:
        in_specs += [
            pl.BlockSpec((1, NH), lambda b, *s: (b, 0)),
            pl.BlockSpec((1, NH), lambda b, *s: (b, 0)),
            pl.BlockSpec((1, NH, KD), lambda b, *s: (b, 0, 0)),
        ]
        operands += [
            m0.astype(jnp.float32),
            l0.astype(jnp.float32),
            acc0.astype(jnp.float32),
        ]
    in_specs.append(pl.BlockSpec((1, NH), lambda b, *s: (0, 0)))
    operands.append(sink_g)

    scratch_shapes = [
        # K/V double-buffers: [2, chunk, PS, KD]
        pltpu.VMEM((2, kv_chunk, PS, KD), k_pages.dtype),
        pltpu.VMEM((2, kv_chunk, PS, KD), v_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if quantized:
        scratch_shapes += [
            # per-token scale double-buffers, lane-major [.., 1, PS]
            pltpu.VMEM((2, kv_chunk, 1, PS), jnp.float32),
            pltpu.VMEM((2, kv_chunk, 1, PS), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    scratch_shapes += [
        pltpu.VMEM((NH, 128), jnp.float32),          # m
        pltpu.VMEM((NH, 128), jnp.float32),          # l
        pltpu.VMEM((NH, KD), jnp.float32),           # block-diag acc
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, NH, Dh), lambda b, *s: (b, 0, 0)),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, Dh), q.dtype),
        # without cross-row warmup, batch rows are independent (disjoint
        # out rows, scratch reinitialized per step) and "parallel" lets
        # megacore TPUs split the grid; the cross-row handoff threads
        # DMA state between steps and needs sequential "arbitrary" rows
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "arbitrary" if cross_row else "parallel",
            ),
        ),
        interpret=interpret,
    )(*scalars, *operands)
