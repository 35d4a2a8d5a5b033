"""Pallas TPU kernel: paged-KV decode attention.

The decode hot loop (SURVEY §7.3 "Paged-KV attention in Pallas"). For each
decode step the jnp fallback gathers a contiguous ``[B, CTX, KVH, Dh]``
view of the page pool per layer — a pure HBM copy that dominates decode
time. This kernel reads K/V pages **in place** with flash-style online
softmax across pages.

Design (fourth generation; the first used grid ``(B, MP)`` with one
BlockSpec-fetched page per grid step and paid a block DMA and ~us of
grid overhead for every table slot, used or not; the second walked the
pages inside the kernel but fetched a FIXED chunk of pages a row, two
slots to a row, and started every row from an empty pipeline: a
280-token row fetched 1 MiB and computed over 512 or 1,024 masked
columns, and its fetch and its arithmetic ran one after the other,
PERF.md §6 PR 31; the third fetched the pages a row holds in a ring
that never drains, below, and took ONE row a grid step: five or six
``(1, ...)`` blocks in and one out, the initialisation, the finalize and
every group's chain for one row with nothing beside them, and a scalar
loop that worked out a row's count and start again for every page it
started, PERF.md §6 PR 55):

- grid ``(B // R,)``: one grid step per BLOCK of ``R`` decode rows, in
  order (``rows_per_step``: the largest of 8, 4, 2, 1 that divides the
  batch and whose blocks and scratch fit the scoped VMEM limit beside
  the ring; ``R = 1`` is the third generation's schedule, and the path
  of a batch no larger block divides). The pipeline brings a block's
  ``q`` / ``k_cur`` / ``v_cur`` / window buffers / prefix carry in and
  its ``out`` back once, ``m / l / acc`` and the block-diagonal queries
  are set up for the ``R`` rows at once, and the finalize runs over
  ``[R, NH, ...]`` (its two window products batched), so its latencies
  are paid once a block and a tp=4 shard's 8-head rows fill the
  sublanes together;
- the operand is the WHOLE stacked pool ``[L, NP, PS, KVH*Dh]``
  (``memory_space=ANY``, HBM-resident) and the layer is a scalar-prefetch
  index: pages are fetched as ``pool.at[layer, page]``. No caller slices
  a layer out of the stack: XLA cannot fuse a slice into a custom call's
  operand and would copy the layer's pool (76 MB at 579 pages) before
  every call;
- **bytes follow the row**: a row fetches ``ceil(past_len / PS)`` pages
  (from ``pfx_cnt`` on under a prefix split), one DMA a page (a page is
  one contiguous block of the fused pool), through its table, so any
  layout is the same code: an ascending run, scattered pages, a shared
  prefix at the table's head;
- **the ring does not drain, and the block did not change it**: the
  call's fetches are ONE sequence over (row, page) in row order, and
  ``ring_shape`` slots of it are in flight: the same slots, alignment
  and bytes whatever ``R``. The cursor lives in SMEM from grid step to
  grid step; when a row's last page has been started the ring goes on
  with the next row's first pages, so a row's arithmetic, a block's
  finalize and the grid's step to the next block run under the fetches
  of the rows after them. Alone the ring moves the 4B cell's pages at
  ~570-640 GB/s, which is where that cell's calls now stand (PERF.md §6
  PR 55): a larger ring did not move it;
- **arithmetic follows the row**: scores and the value product run over
  the groups of pages that landed (``GROUP_TOKENS`` columns a group),
  one ``[NH, GT]`` block-diagonal score matmul and one value matmul a
  group for all KV heads, accumulating ``(m, l, acc)`` in VMEM scratch.
  A row's groups, their sizes and their order are what a grid step of
  its own gave it, so a row's result is the same sums in the same
  order: the block is a schedule, not mathematics;
- **two rows' chains stand side by side** where the ring holds both
  rows' pages and still keeps a largest group's slots for the fetches
  ahead: a group is one serial chain (matmul, max, exp, sum, matmul),
  and the next row's chain of the same size fills its latencies. Rows
  that do not fit together (two 8-page rows of the 4B cell's 16 slots)
  go one after the other as before: side by side they emptied the ring
  behind them;
- the current token's K/V, the optional multi-step decode window buffer
  (tokens sampled in the current fused window, not yet written to the
  pool — see engine/runner.decode_multi), and the optional gpt-oss
  attention sink all join the softmax in the finalization step;
- the layer index and per-layer sliding windows (Gemma3 / gpt-oss) are
  dynamic operands, so one compiled kernel serves every layer of the
  ``lax.scan``: such a layer's pages before its window are fetched and
  masked (its pool keeps them anyway);
- a layer whose pool is the WINDOW pool (``window_start``, static: a
  "swa" layer of a model that lists its layers by kind,
  engine/kvcache.py) fetches from the page that holds position
  ``max(pos - window + 1, 0)`` on, the later of that and the shared
  prefix's end: the pages before it have slid out of the window, and
  the host may have given them to another sequence. A call without it
  (every call of a model with one pool) compiles to the program it had;
- a row's SELECTION (``keep`` / ``keep_tail``, static likewise: a latent
  layer under an indexer, ops/sparse_attention.selected_decode) is one
  more operand of the same body: a block's ``[R, MP * PS]`` int32 slab
  beside ``q`` (64 KB a row at a table of 256 pages), of which a group
  ANDs its columns' slice with the length test, and a tile a row for
  the window's slots and the own row in the finalize. The ring, the
  groups and their order are the dense call's: the pages are walked,
  not skipped. A call without it traces the program it had
  (tests/test_selected_decode_kernel.py holds the jaxpr's text).

All math is float32, but the two products of the latent variant, whose
operands are the pool's dtype (float32 accumulation).
"""

from __future__ import annotations

import functools
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
    ring_pages: int,
    group_pages: int,
    window_slots: int = 0,
    quantized: bool = False,
    prefix: bool = False,
    window_start: bool = False,
    shared: bool = False,
    rows: int = 1,
    keep: bool = False,
):
    # ref layout varies with (window_slots, quantized, prefix, shared,
    # keep) —
    # walk an index instead of a per-case tuple unpack. ``shared``: ONE
    # pool whose rows serve both products (a latent layer's: module
    # docstring), so there is no V pool, current V, window V or V ring
    it = iter(refs)
    page_table_ref = next(it)
    past_len_ref = next(it)
    window_ref = next(it)
    layer_ref = next(it)
    pfx_cnt_ref = next(it) if prefix else None
    win_len_ref = next(it) if window_slots else None
    q_ref = next(it)
    k_pool_ref = next(it)
    v_pool_ref = None if shared else next(it)
    ks_pool_ref = next(it) if quantized else None
    vs_pool_ref = next(it) if quantized else None
    k_cur_ref = next(it)
    v_cur_ref = None if shared else next(it)
    wk_ref = next(it) if window_slots else None
    wv_ref = next(it) if window_slots and not shared else None
    m0_ref = next(it) if prefix else None
    l0_ref = next(it) if prefix else None
    acc0_ref = next(it) if prefix else None
    # a row's SELECTION (module docstring): its paged positions in table
    # order, a fused window's pending slots, the step's own row
    keep_ref = next(it) if keep else None
    keep_win_ref = next(it) if keep and window_slots else None
    keep_own_ref = next(it) if keep else None
    sink_ref = next(it)
    out_ref = next(it)
    kbuf = next(it)
    vbuf = None if shared else next(it)
    ksem = next(it)
    vsem = None if shared else next(it)
    ksbuf = next(it) if quantized else None
    vsbuf = next(it) if quantized else None
    kssem = next(it) if quantized else None
    vssem = next(it) if quantized else None
    ring = next(it)
    qbd_ref = next(it)
    m_ref = next(it)
    l_ref = next(it)
    acc_ref = next(it)

    R = rows  # rows a grid step
    b0 = pl.program_id(0) * R  # the block's first row
    B = pl.num_programs(0) * R
    MP = max_pages_per_seq
    PS = page_size
    D = ring_pages
    GP = group_pages  # pages of the largest group, a power of two
    # GP, GP/2, ... 1
    group_sizes = [GP >> i for i in range(GP.bit_length())]
    NH = q_ref.shape[1]
    Dh = q_ref.shape[2]
    G = NH // kvh
    KD = kvh * Dh

    win = window_ref[0]
    # the pools are the whole [L, NP, PS, KD] stacks, resident in HBM:
    # every DMA below indexes [layer, page] itself
    layer = layer_ref[0]

    # -- the fetch ring ------------------------------------------------
    # The call's fetches are ONE sequence: row 0's pages, then row 1's,
    # ... each row's from its first page (past the shared prefix, whose
    # carry arrives computed) to the page that holds its last token.
    # ``ring`` (SMEM, kept from grid step to grid step) is the cursor:
    # [0] the row and [1] the page of the next fetch to start, [2] its
    # slot number, [3] the slot number the consumer has read up to.
    # Slot numbers only grow; slot number t lives in ring slot t % D. A
    # row's pages take consecutive slot numbers from a multiple of the
    # power of two that covers them (at most GP), so that every group
    # the arithmetic takes (below) is one contiguous slab of the ring,
    # aligned to its own size; the numbers that alignment skips are
    # not fetched.

    def first_page(row):
        first = pfx_cnt_ref[row] if prefix else 0
        if window_start:
            # the page of the oldest position the row's query can see
            # (every row's query is win_len past its pages' tokens)
            oldest = past_len_ref[row] + (
                win_len_ref[0] if window_slots else 0
            ) - win + 1
            slid = jnp.where(win > 0, jnp.maximum(oldest, 0) // PS, 0)
            first = jnp.maximum(first, slid)
        return first

    def pages_of(row):
        """Pages row fetches: up to its last token's, from first_page."""
        n = (past_len_ref[row] + PS - 1) // PS - first_page(row)
        return jnp.maximum(n, 0)

    def aligned(t, n):
        """``t`` rounded up to where a row of ``n`` pages may start."""
        a = jnp.int32(1)
        for size in group_sizes[::-1][1:]:
            a = jnp.where(n > size // 2, size, a)
        # ``a`` is a power of two: no division on the scalar core
        return jnp.bitwise_and(t + a - 1, -a)

    def slot_dmas(page, s):
        """The copies of pool page ``page`` into ring slot ``s``: K, V
        and, under int8, their scales (pre-shaped [L, NP, 1, PS] so a
        page's scales land lane-major, a legal [1, PS] broadcast against
        a score slice; merging sublanes into lanes in-kernel is
        unsupported)."""
        dmas = [
            pltpu.make_async_copy(
                k_pool_ref.at[layer, page], kbuf.at[s], ksem.at[s]
            ),
        ]
        if not shared:
            dmas.append(pltpu.make_async_copy(
                v_pool_ref.at[layer, page], vbuf.at[s], vsem.at[s]
            ))
        if quantized:
            dmas += [
                pltpu.make_async_copy(
                    ks_pool_ref.at[layer, page], ksbuf.at[s], kssem.at[s]
                ),
                pltpu.make_async_copy(
                    vs_pool_ref.at[layer, page], vsbuf.at[s], vssem.at[s]
                ),
            ]
        return dmas

    def top_up(limit):
        """Start fetches, in sequence order, while their slot number is
        under ``limit`` (the consumer's position + D: the slot's last
        occupant has been read) and rows remain. It runs on from a
        row's last page into the next row's first, over rows that fetch
        nothing, so the ring never drains between rows. What a row
        costs the scalar core (its count, its first page, where it may
        start) is worked out once a visit, and a page costs its table
        entry and its copies' descriptors."""

        def more(c):
            row, _, t = c
            return jnp.logical_and(row < B, t < limit)

        def step(c):
            row, j, t = c
            n = pages_of(row)
            t = jnp.where(j == 0, aligned(t, n), t)
            count = jnp.maximum(jnp.minimum(n - j, limit - t), 0)
            entry = row * MP + first_page(row) + j

            def start(i, _):
                for dma in slot_dmas(
                    page_table_ref[entry + i], jax.lax.rem(t + i, D)
                ):
                    dma.start()
                return 0

            jax.lax.fori_loop(0, count, start, 0)
            j, t = j + count, t + count
            done = j >= n
            return jnp.where(done, row + 1, row), jnp.where(done, 0, j), t

        row, j, t = jax.lax.while_loop(
            more, step, (ring[0], ring[1], ring[2])
        )
        ring[0], ring[1], ring[2] = row, j, t

    @pl.when(b0 == 0)
    def _open_ring():
        for i in range(4):
            ring[i] = 0

    # Block-diagonal queries: fold the per-KV-head loop into ONE score
    # matmul and ONE value matmul per group. Row i (= head i, KV head
    # i // G) of q_bd carries q[i] in column block i // G of the fused
    # [KVH*Dh] axis and zeros elsewhere, so q_bd @ k_group.T computes
    # every head's scores in a single MXU op (the off-block FLOPs are
    # wasted: 2*KVH tiny per-head dots per group cost ~3x more wall
    # time than these two). Mosaic cannot merge (KVH, Dh) into the
    # lane dim in-kernel, so the page pool arrives pre-fused [.., KD]
    # and lane-space masks are built from iota instead of reshapes.
    # Built for the block's R rows at once and kept in VMEM: the row
    # loop below reads its row's.
    q = q_ref[...].astype(jnp.float32)                    # [R, NH, Dh]
    row_head = jax.lax.broadcasted_iota(jnp.int32, (NH, KD), 0) // G
    col_head = jax.lax.broadcasted_iota(jnp.int32, (NH, KD), 1) // Dh
    blk_kd = (row_head == col_head).astype(jnp.float32)   # [NH, KD]
    q_rep = jnp.concatenate([q] * kvh, axis=2)            # [R, NH, KD]
    qbd_ref[...] = q_rep * blk_kd[None]

    # Shared-prefix (Hydragen-style) mode: the first pfx_cnt pages of
    # a row's table hold a prefix whose K/V is SHARED with other
    # rows. Their attention was computed ONCE for the whole batch
    # outside the kernel (prefix_attention_carry — the pages are read
    # from HBM once instead of once per row) and arrives as the initial
    # online-softmax carry; the row's fetches start AFTER them.
    # Non-member rows carry (m=-inf, l=0, acc=0) — exactly the cold
    # init — and start at page 0. Online softmax is associative, so the
    # result is bit-comparable to walking the prefix pages in-row.
    if prefix:
        # m0 / l0 arrive [R, NH, 1]: heads on the sublanes, as m / l
        # keep them
        m_ref[...] = jnp.broadcast_to(m0_ref[...], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l0_ref[...], l_ref.shape)
        acc_ref[...] = acc0_ref[...]
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Arithmetic follows the row: its pages are taken in groups of the
    # sizes of their count's binary digits, largest first (13 pages: a
    # group of 8, of 4, of 1, when GP is 8), so that no column past the
    # row's last page is computed and the m / l / acc update is paid a
    # few times a row, not once a page. A group is one serial chain
    # (matmul, max, exp, sum, matmul) whose latencies nothing of its
    # own row can fill. So two rows of the block whose pages the ring
    # holds together go through their digits side by side: where both
    # have a digit, the two chains stand in ONE straight-line body (a
    # row's state is read before either chain and written after both,
    # so that nothing orders one chain behind the other), and where one
    # has it, its chain runs alone. One body a size and a width,
    # whichever rows of the block run it.

    def wait_pages(slot, size):
        # a wait reads its copy's semaphore and size: any page stands
        # for the one that was fetched
        for o in range(size):
            for dma in slot_dmas(0, slot + o):
                dma.wait()

    def slot_of(t, size):
        # a row started at a multiple of a power of two >= size, and its
        # larger groups came first: a group's slab of the ring is
        # aligned to ``size``
        return pl.multiple_of(jax.lax.rem(t, D), size)

    def load(r):
        return qbd_ref[r], m_ref[r, :, 0], l_ref[r, :, 0], acc_ref[r]

    def store(r, m_new, l_new, acc_new):
        m_ref[r] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
        l_ref[r] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])
        acc_ref[r] = acc_new

    def chain(b, t, done, size, state):
        """Scores and values of ``size`` pages from row ``b``'s
        ``done``-th on, which lie from slot number ``t``, folded into
        the row's ``state``: (q_bd, m, l, acc) in, (m, l, acc) out."""
        q_bd, m_prev, l_prev, acc_prev = state
        past = past_len_ref[b]
        # current token's global position: tokens already in pages plus
        # any fused-window tokens not yet written back
        pos = past + (win_len_ref[0] if window_slots else 0)
        GT = size * PS
        slot = slot_of(t, size)
        tok = (first_page(b) + done) * PS + jax.lax.broadcasted_iota(
            jnp.int32, (NH, GT), 1
        )
        ok = tok < past
        # windowless (win <= 0) ORed in instead of a boolean select —
        # Mosaic cannot legalize arith.select on i1 vectors
        ok = jnp.logical_and(
            ok, jnp.logical_or(pos - tok < win, win <= 0)
        )
        if keep:
            # the group's slice of the selection: whole lane tiles of
            # the block's slab from where the group's first column lies
            # (a row under a selection starts at page 0, and ``done`` is
            # a multiple of the group's own size, of twice it under a
            # binary digit: 128 lanes at pages of 64), the row's sublane
            # picked by a compare (a load at a dynamic sublane is not
            # there to be had) and widened over the heads as int32 (nor
            # is a broadcast of i1 vectors)
            lanes = -(-GT // 128) * 128
            at = pl.multiple_of(done * PS, GT if size == GP else 2 * GT)
            slab = keep_ref[0, :, pl.ds(at, lanes)]          # [R, lanes]
            mine = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0) == b - b0
            kept = jnp.max(
                jnp.where(mine, slab, 0), axis=0, keepdims=True
            )[:, :GT]
            ok = jnp.logical_and(ok, jnp.broadcast_to(kept, (NH, GT)) > 0)
        # [size, PS, KD] -> [GT, KD]: leading-dim collapse only (the
        # lane dim KD is untouched — Mosaic supports this shape cast)
        if shared:
            # the page as it landed serves both products, in the pool's
            # dtype (float32 accumulation): at NH rows to one 640-wide
            # key a float32 product of the MXU, not the fetch, would
            # bound the step
            k = v = kbuf[pl.ds(slot, size)].reshape(GT, KD)
            q_in = q_bd.astype(k.dtype)
        else:
            k = kbuf[pl.ds(slot, size)].reshape(GT, KD).astype(jnp.float32)
            v = vbuf[pl.ds(slot, size)].reshape(GT, KD).astype(jnp.float32)
            q_in = q_bd
        s = jax.lax.dot_general(
            q_in, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [NH, GT]
        if quantized:
            # K dequant folds into the scores: q.(k_int*ks) = (q.k_int)*ks
            # — one [1, PS] lane-broadcast multiply per page of the
            # group, lane-concatenated back to [NH, GT]
            s = jnp.concatenate(
                [
                    s[:, pg * PS : (pg + 1) * PS] * ksbuf[slot + pg]
                    for pg in range(size)
                ],
                axis=1,
            )
        s = jnp.where(ok, s, NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))  # [NH]
        alpha = jnp.exp(m_prev - m_new)                  # [NH]
        p = jnp.exp(s - m_new[:, None])                  # [NH, GT]
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        if quantized:
            # V dequant folds into the probabilities for the value dot
            # ONLY — the normalizer l above sums the true p:
            # p.(v_int*vs) = (p*vs).v_int
            pv = jnp.concatenate(
                [
                    p[:, pg * PS : (pg + 1) * PS] * vsbuf[slot + pg]
                    for pg in range(size)
                ],
                axis=1,
            )
        else:
            pv = p.astype(v.dtype)       # float32 but for a shared pool
        # acc holds the full [NH, KVH*Dh] product; only each row's own
        # head block is meaningful (extracted at the end)
        acc_new = acc_prev * alpha[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    def groups(size, low, rows):
        """The group of ``size`` pages of each of ``rows`` (one, or two
        side by side): ``(r, t0, done)`` a row, its index in the block,
        its first slot number and the pages it has folded. ``low`` is
        the first slot number not yet read."""
        # the pages before ``low`` have been read: refill their slots
        top_up(low + D)
        for r, t0, done in rows:
            wait_pages(slot_of(t0 + done, size), size)
        states = [load(r) for r, _, _ in rows]
        new = [
            chain(b0 + r, t0 + done, done, size, state)
            for (r, t0, done), state in zip(rows, states)
        ]
        for (r, _, _), out in zip(rows, new):
            store(r, *out)

    def digits(r, t_end):
        """The block's rows from the ``r``-th on, ``t_end`` the slot
        number read up to: that row, and the next beside it where the
        ring holds both rows' pages at once and keeps a largest
        group's slots for the fetches ahead (two 8-page rows of the 4B
        cell fill its 16 slots: side by side they ran 30 % slower than
        one after the other, the ring standing empty behind them;
        PERF.md section 6 PR 55). A row's groups keep the sizes and the
        order a grid step of its own gave them."""
        n_a = pages_of(b0 + r)
        t_a = aligned(t_end, n_a)  # the row's first slot number
        end_a = t_a + n_a
        # the row after it (the block's last row has none: it stands
        # beside itself with no pages, as every row of a block of one)
        r_b = jnp.minimum(r + 1, R - 1)
        n_b = pages_of(b0 + r_b)
        t_b = aligned(end_a, n_b)
        pair = jnp.logical_and(r + 1 < R, t_b + n_b - t_a <= D - GP)
        n_b = jnp.where(pair, n_b, 0)
        end = jnp.where(pair, t_b + n_b, end_a)

        def low(done_a, done_b):
            # the ring refills behind the first page not yet read: the
            # first row's while it has any
            return jnp.where(done_a < n_a, t_a + done_a, t_b + done_b)

        def both(done_a, done_b, size):
            groups(
                size, low(done_a, done_b),
                [(r, t_a, done_a), (r_b, t_b, done_b)],
            )

        def one(done_a, done_b, size, first):
            # the row that has the digit, alone
            row = tuple(
                jnp.where(first, a, b) for a, b in
                ((r, r_b), (t_a, t_b), (done_a, done_b))
            )
            groups(size, low(done_a, done_b), [row])

        # a block of one row builds no body of two chains
        w_a, w_b = n_a // GP, n_b // GP
        w_ab = jnp.minimum(w_a, w_b)
        if R > 1:

            def whole_both(gi, _):
                both(gi * GP, gi * GP, GP)
                return 0

            jax.lax.fori_loop(0, w_ab, whole_both, 0)

        def whole_one(gi, _):
            one(
                jnp.minimum(gi, w_a) * GP, jnp.minimum(gi, w_b) * GP, GP,
                w_a > w_b,
            )
            return 0

        jax.lax.fori_loop(w_ab, jnp.maximum(w_a, w_b), whole_one, 0)
        for size in group_sizes[1:]:
            # the pages under this binary digit of a row's count
            has_a = jax.lax.rem(n_a, 2 * size) >= size
            has_b = jax.lax.rem(n_b, 2 * size) >= size
            done_a = n_a // (2 * size) * (2 * size)
            done_b = n_b // (2 * size) * (2 * size)
            if R > 1:

                @pl.when(jnp.logical_and(has_a, has_b))
                def _both(size=size, done_a=done_a, done_b=done_b):
                    both(done_a, done_b, size)

            @pl.when(jnp.logical_xor(has_a, has_b))
            def _one(size=size, done_a=done_a, done_b=done_b, has_a=has_a):
                one(done_a, done_b, size, has_a)

        return r + 1 + pair.astype(jnp.int32), end

    # the block's rows in the ring's order, a loop and not R copies of
    # the group bodies
    _, ring[3] = jax.lax.while_loop(
        lambda c: c[0] < R, lambda c: digits(*c), (jnp.int32(0), ring[3])
    )
    # the block's pages have been read: the fetches that take their
    # slots are the next blocks', and they run under this block's
    # finalize and the grid's step to the next block
    top_up(ring[3] + D)

    # finalize, the block's R rows at once: fused-window tokens +
    # current token + attention sink, in the same block-diagonal space
    # (2 batched dots a block, not 2 per head of every row). Its chain
    # (product, max, exp, sum, product, extraction) is latency a row
    # alone cannot fill, so the block pays it once: a tp=4 shard's rows
    # of 8 heads fill the sublanes together, and even rows that fill the
    # registers alone finish sooner together than one by one in a loop
    # (PERF.md section 6 PR 55)
    W = window_slots
    sink = sink_ref[...][None]                           # [1, NH, 1]
    q_bd = qbd_ref[...]                                  # [R, NH, KD]
    k_cur = k_cur_ref[...].astype(jnp.float32)           # [R, 1, KD]
    v_cur = k_cur if shared else v_cur_ref[...].astype(jnp.float32)
    # one key: a lane reduction, not a matmul with a single column
    s_self = jnp.sum(q_bd * k_cur, axis=2, keepdims=True) * scale
    if keep:
        # the step's own row can fall out of a selection too
        own_kept = jnp.broadcast_to(keep_own_ref[...], (R, NH, 1)) > 0
        s_self = jnp.where(own_kept, s_self, NEG_INF)
    m_prev = m_ref[:, :, :1]                             # [R, NH, 1]
    m_new = jnp.maximum(m_prev, jnp.maximum(s_self, sink))
    if W:
        # window tokens: slot s holds the fused window's s-th sampled
        # token at position past+s; the query is at pos
        wlen = win_len_ref[0]
        wk = wk_ref[...].astype(jnp.float32)             # [R, W, KD]
        wv = wk if shared else wv_ref[...].astype(jnp.float32)
        slot_i = jax.lax.broadcasted_iota(jnp.int32, (R, NH, W), 2)
        ok_w = slot_i < wlen
        ok_w = jnp.logical_and(
            ok_w,
            jnp.logical_or(wlen - slot_i < win, win <= 0),
        )
        if keep:
            ok_w = jnp.logical_and(
                ok_w, jnp.broadcast_to(keep_win_ref[...], (R, NH, W)) > 0
            )
        s_w = jax.lax.dot_general(
            q_bd, wk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [R, NH, W]
        s_w = jnp.where(ok_w, s_w, NEG_INF)
        m_new = jnp.maximum(m_new, jnp.max(s_w, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p_self = jnp.exp(s_self - m_new)
    p_sink = jnp.exp(sink - m_new)
    l = l_ref[:, :, :1] * alpha + p_self + p_sink        # [R, NH, 1]
    acc = acc_ref[...] * alpha + p_self * v_cur          # [R, NH, KD]
    if W:
        p_w = jnp.exp(s_w - m_new)                       # [R, NH, W]
        l = l + jnp.sum(p_w, axis=2, keepdims=True)
        acc = acc + jax.lax.dot_general(
            p_w, wv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
    # extract each row's own head block from the block-diagonal acc:
    # lane block j belongs to the query heads of KV head j
    own = jax.lax.broadcasted_iota(jnp.int32, (R, NH, Dh), 1) // G
    acc_bd = jnp.zeros((R, NH, Dh), jnp.float32)
    for j in range(kvh):
        acc_bd = acc_bd + jnp.where(
            own == j, acc[:, :, j * Dh : (j + 1) * Dh], 0.0
        )
    out = acc_bd / jnp.maximum(l, 1e-30)
    out_ref[...] = out.astype(out_ref.dtype)


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


# The fetch ring's size. Pages in flight cost VMEM (K and V a slot) and
# buy cover for the DMA latency: ~2 us x 819 GB/s = 1.6 MB in flight
# keeps v5e's HBM busy. 4 MiB is what the two 1 MiB chunk slots of the
# second-generation schedule cost; 32 slots bound the semaphores where
# pages are small (a tp=4 shard's 32 KB page: 2 MiB in flight).
RING_BYTES = 4 << 20
RING_MAX_PAGES = 32
# Score columns of the largest group the arithmetic takes at once. A
# group costs about the same whatever its width up to here (the chain
# matmul, max, exp, sum, matmul, rescale is latency, PERF.md §6 PR 31),
# so a row is taken in as few groups as its page count has binary
# digits; past 512 columns the float32 operands of one group outgrow
# what is worth keeping in VMEM beside the ring.
GROUP_TOKENS = 512


def ring_shape(page_size: int, kd: int, dtype_bytes: int, max_pages: int):
    """``(ring_pages, group_pages)`` of the fetch ring for pages of
    ``[page_size, kd]``: the pages of the largest compute group (a
    power of two, at most a row's table) and the ring's slots (a
    multiple of that group, at least two of it so that one lands while
    one is read)."""
    group = max(1, min(GROUP_TOKENS // page_size, max_pages))
    group = 1 << (group.bit_length() - 1)
    page_bytes = max(page_size * kd * dtype_bytes, 1)
    pages = min(RING_BYTES // (2 * page_bytes), RING_MAX_PAGES)
    return max(pages // group, 2) * group, group


# What a kernel's blocks, scratch and temporaries may take together:
# Mosaic's default scoped VMEM limit on v5e (16 MiB of its 128).
VMEM_LIMIT_BYTES = 16 << 20
ROWS_A_STEP = (8, 4, 2, 1)


def decode_vmem_bytes(
    rows: int, NH: int, Dh: int, KD: int, PS: int, D: int, GP: int, W: int,
    *, pool_bytes: int, io_bytes: int, prefix: bool = False,
    shared: bool = False, quantized: bool = False, keep_lanes: int = 0,
) -> int:
    """VMEM a call of ``rows`` rows a grid step takes, as the shapes
    tell it: the ring, a group's scores, the pipeline's two buffers of
    every block (``keep_lanes``: the int32 lanes of a row's selection,
    0 without one), the scratch, and the finalize's values over the
    block.
    (The compiler's own count at the 4B cell's shape, 32 rows a step,
    was 18.1 MiB where this says 34.5: the blocks of one sublane pad
    less than a tile.)"""

    def tile(sub, lanes, itemsize):
        # a (sub, lanes) slab as VMEM holds it: whole (8, 128) tiles of
        # 32 bits, so narrower values pad further
        pack = 4 // itemsize
        return (
            -(-sub // (8 * pack)) * 8 * pack * -(-lanes // 128) * 128
            * itemsize
        )

    both = 1 if shared else 2
    ring = both * D * tile(PS, KD, pool_bytes)
    if quantized:
        ring += 2 * D * tile(1, PS, 4)
    # scores, probabilities and mask of the largest group, two rows'
    # side by side (its K and V are read from the ring tile by tile)
    group = 2 * 4 * tile(NH, GP * PS, 4)
    row_f32 = tile(NH, KD, 4)
    blocks = 2 * tile(NH, Dh, io_bytes)              # q, out
    blocks += both * tile(1, KD, io_bytes)           # current K, V
    blocks += both * tile(W, KD, io_bytes) if W else 0
    if prefix:
        blocks += 2 * tile(NH, 1, 4) + row_f32       # m0, l0, acc0
    scratch = 2 * row_f32 + 2 * tile(NH, 128, 4)     # q_bd, acc, m, l
    finalize = 3 * row_f32
    # a block's selection, two buffers: its rows' paged positions (ONE
    # slab, a row a sublane), the window's slots and the own row's
    selection = 2 * (
        tile(rows, keep_lanes, 4) + 2 * rows * tile(1, 128, 4)
    ) if keep_lanes else 0
    return (
        ring + group + selection
        + rows * (2 * blocks + scratch + finalize)
    )


def rows_per_step(B: int, *shape, **modes) -> int:
    """Rows a grid step of the kernel takes: the largest of 8, 4, 2, 1
    that divides the batch and fits ``VMEM_LIMIT_BYTES`` beside the ring
    (``decode_vmem_bytes``'s arguments after ``rows``). A batch no
    larger block divides, or rows too wide for two, runs a row a step."""
    for rows in ROWS_A_STEP:
        if rows == 1 or (
            B % rows == 0
            and decode_vmem_bytes(rows, *shape, **modes) <= VMEM_LIMIT_BYTES
        ):
            return rows
    return 1


def paged_decode_supported(
    q: jax.Array, k_pages: jax.Array, selection_pages: int = 0,
) -> bool:
    """Shape/size gate for the compiled TPU path (interpret mode has no
    such constraints — tests call paged_decode_attention(interpret=True)).
    ``selection_pages``: the pages of a row's table where the call is
    under a SELECTION (``keep`` [B, pages * PS]); then pages of whole or
    half lane tiles and a table of two pages or more, so that every
    group's slice of the selection starts on a lane tile (``chain``)."""
    Dh = q.shape[-1]
    PS = k_pages.shape[2]
    if selection_pages and not (
        PS % 64 == 0 and (PS % 128 == 0 or selection_pages >= 2)
    ):
        return False
    return Dh % 128 == 0 and PS % 8 == 0


def _keep_lanes(PS: int, MP: int) -> int:
    """Lanes of a row's selection as the kernel holds it: the table's
    positions up to whole lane tiles, and a tile more where pages are
    no half tile (a group's slice is read in whole tiles from a start
    that is then on no tile: interpret mode alone, the gate above)."""
    return -(-(MP * PS + (0 if PS % 64 == 0 else 128)) // 128) * 128


@functools.partial(
    jax.jit, static_argnames=("interpret", "window_start", "scale", "rows")
)
def paged_decode_attention(
    q: jax.Array,          # [B, NH, Dh] — current-step queries
    k_pages: jax.Array,    # [L, NP, PS, KVH*Dh] — the stacked FUSED pool
    v_pages: Optional[jax.Array],   # None: ``k_pages`` serves both products
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
    interpret: bool = False,
    # int8 KV mode: pages are int8 and these carry the per-token
    # dequant scales [L, NP, PS] f32 (engine/kvcache.py)
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    # shared-prefix (Hydragen-style) mode: rows whose table head holds a
    # job-shared prefix skip those pages (pfx_cnt[b] of them) and start
    # from the injected online-softmax carry (prefix_attention_carry) —
    # the shared pages are then read from HBM once per step for the
    # whole batch instead of once per row.
    pfx_cnt: Optional[jax.Array] = None,   # [B] int32 pages to skip
    m0: Optional[jax.Array] = None,        # [B, NH] f32
    l0: Optional[jax.Array] = None,        # [B, NH] f32
    acc0: Optional[jax.Array] = None,      # [B, NH, KVH*Dh] f32 (block-diag)
    # the pool holds a row's last ``window`` positions only: fetch from
    # the page of position max(pos - window + 1, 0) on (module docstring)
    window_start: bool = False,
    # the softmax scale where it is not 1/sqrt(Dh) (a latent layer's)
    scale: Optional[float] = None,
    # rows a grid step; None: what ``rows_per_step`` gives the call's
    # shapes (tests and benchmarks/paged_kernel_ab.py name one)
    rows: Optional[int] = None,
    # a row's SELECTION (the latent variant under an indexer,
    # ops/sparse_attention.selected_decode): ``keep`` [B, MP * PS], the
    # row's paged positions in table order, and ``keep_tail`` [B, W + 1],
    # the window's pending slots and the step's own row; bool or any
    # integer, nonzero = attended. A position past ``past_len`` or a
    # slot past ``win_len`` stays out whatever they say
    keep: Optional[jax.Array] = None,
    keep_tail: Optional[jax.Array] = None,
) -> jax.Array:
    """Returns [B, NH, Dh] attention outputs for one decode step.

    ``v_pages`` None is the LATENT variant (models/transformer.py
    ``mla_mixer``, the absorbed form): ``k_pages`` is a pool of ONE row
    a token, ``[L, NP, PS, Dh]``, that all ``NH`` heads read for both
    products (``KVH`` 1; ``q`` carries the key half of the
    up-projection), so a row's pages are fetched ONCE, the value
    product runs over the whole row (the caller keeps its leading
    latent values) and ``k_cur`` / ``win_k`` stand for the values too
    (``v_cur`` / ``win_v`` None).

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
    the multi-GB pool is never copied per step.

    ``keep`` / ``keep_tail`` put the softmax under a row's SELECTION:
    the row's pages are walked as without one (bytes follow the row's
    LENGTH: a selection of a third of the positions leaves runs of a row
    or two, nothing a page DMA could skip), and a group's columns are
    ANDed with the row's slice of ``keep`` where the length test stands;
    the pending slots and the own row with ``keep_tail`` in the
    finalize. At least one candidate of a row is kept (the caller's
    top-k of one or more), which is what lets a masked column's ``exp``
    be forgotten as a column past the row's end is. Static, as
    ``window_start``: a call without them traces the program it had."""
    B, NH, Dh = q.shape
    L, NP, PS, KD = k_pages.shape
    KVH = k_cur.shape[1]
    MP = page_table.shape[1]
    scale = Dh ** -0.5 if scale is None else scale
    W = 0 if win_k is None else win_k.shape[1]
    shared = v_pages is None
    selecting = keep is not None
    keep_lanes = _keep_lanes(PS, MP) if selecting else 0
    if selecting:
        # a selection is a latent layer's, whose pool is one and whole
        assert shared and pfx_cnt is None and not window_start
        assert keep.shape == (B, MP * PS), (keep.shape, B, MP, PS)
        assert keep_tail.shape == (B, W + 1), (keep_tail.shape, W)

    # heads on the sublanes, as the kernel keeps m / l
    if sink is None:
        sink_g = jnp.full((NH, 1), NEG_INF, jnp.float32)
    else:
        sink_g = sink.astype(jnp.float32).reshape(NH, 1)

    quantized = k_scale is not None
    prefix = pfx_cnt is not None
    D, GP = ring_shape(PS, KD, k_pages.dtype.itemsize, MP)
    R = rows or rows_per_step(
        B, NH, Dh, KD, PS, D, GP, W,
        pool_bytes=k_pages.dtype.itemsize, io_bytes=q.dtype.itemsize,
        prefix=prefix, shared=shared, quantized=quantized,
        keep_lanes=keep_lanes,
    )
    assert B % R == 0, (B, R)
    lowering.record_kernel(
        "paged_decode", interpret=interpret, rows=R, heads=q.shape[1],
        form={"select": "keep"} if selecting else None,
    )
    kernel = functools.partial(
        _paged_decode_kernel,
        max_pages_per_seq=MP,
        page_size=PS,
        scale=scale,
        kvh=KVH,
        ring_pages=D,
        group_pages=GP,
        window_slots=W,
        quantized=quantized,
        prefix=prefix,
        window_start=window_start,
        shared=shared,
        rows=R,
        keep=selecting,
    )

    # a grid step's block of each per-row operand: its R rows. Index
    # maps take *s so the scalar-prefetch arity (4 to 6) needs no
    # per-case lambdas
    def rows_of(*dims):
        return pl.BlockSpec((R, *dims), lambda g, *s: (g,) + (0,) * len(dims))

    in_specs = [
        rows_of(NH, Dh),
        pl.BlockSpec(memory_space=pl.ANY),  # K pool stays in HBM
    ]
    if not shared:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # V pool too
    scalars = [
        page_table.reshape(-1).astype(jnp.int32),
        past_len.astype(jnp.int32),
        jnp.asarray(window, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
    ]
    if prefix:
        scalars.append(pfx_cnt.astype(jnp.int32))
    operands = [q, k_pages] if shared else [q, k_pages, v_pages]
    if quantized:
        # pre-shaped [L, NP, 1, PS] (a bitcast of the stack): a page's
        # scales land lane-major (see page_dmas)
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        operands += [
            k_scale.astype(jnp.float32).reshape(L, NP, 1, PS),
            v_scale.astype(jnp.float32).reshape(L, NP, 1, PS),
        ]
    # K then V; K alone where one pool's rows serve both
    both = 1 if shared else 2
    in_specs += [rows_of(1, KD)] * both
    operands += [x.reshape(B, 1, KD) for x in (k_cur, v_cur)[:both]]
    if W:
        scalars.append(jnp.asarray(win_len, jnp.int32).reshape(1))
        in_specs += [rows_of(W, KD)] * both
        operands += [win_k, win_v][:both]
    if prefix:
        # m0 / l0 as [B, NH, 1]: heads on the sublanes, and a block's
        # last two dims are the array's whole
        in_specs += [rows_of(NH, 1), rows_of(NH, 1), rows_of(NH, KD)]
        operands += [
            m0.astype(jnp.float32).reshape(B, NH, 1),
            l0.astype(jnp.float32).reshape(B, NH, 1),
            acc0.astype(jnp.float32),
        ]
    if selecting:
        # int32, a block of R rows ONE [R, lanes] slab of [B // R, R,
        # lanes] (its last two dims the array's whole: an int8 block of
        # 8 rows is under Mosaic's (32, 128) tile), zeros behind the
        # table's end up to whole lane tiles
        paged = jnp.pad(
            keep.astype(jnp.int32), ((0, 0), (0, keep_lanes - MP * PS))
        ).reshape(B // R, R, keep_lanes)
        tail = keep_tail.astype(jnp.int32).reshape(B, 1, W + 1)
        in_specs.append(
            pl.BlockSpec((1, R, keep_lanes), lambda g, *s: (g, 0, 0))
        )
        operands.append(paged)
        if W:
            in_specs.append(rows_of(1, W))
            operands.append(tail[..., :W])
        in_specs.append(rows_of(1, 1))
        operands.append(tail[..., W:])
    in_specs.append(pl.BlockSpec((NH, 1), lambda g, *s: (0, 0)))
    operands.append(sink_g)

    # the K/V ring: D page slots (K's alone for a shared pool)
    ring_bufs = [pltpu.VMEM((D, PS, KD), k_pages.dtype)]
    if not shared:
        ring_bufs.append(pltpu.VMEM((D, PS, KD), v_pages.dtype))
    scratch_shapes = ring_bufs + [pltpu.SemaphoreType.DMA((D,))] * both
    if quantized:
        scratch_shapes += [
            # per-token scales of each slot, lane-major [.., 1, PS]
            pltpu.VMEM((D, 1, PS), jnp.float32),
            pltpu.VMEM((D, 1, PS), jnp.float32),
            pltpu.SemaphoreType.DMA((D,)),
            pltpu.SemaphoreType.DMA((D,)),
        ]
    scratch_shapes += [
        pltpu.SMEM((4,), jnp.int32),                 # the ring's cursor
        pltpu.VMEM((R, NH, KD), jnp.float32),        # block-diag queries
        pltpu.VMEM((R, NH, 128), jnp.float32),       # m
        pltpu.VMEM((R, NH, 128), jnp.float32),       # l
        pltpu.VMEM((R, NH, KD), jnp.float32),        # block-diag acc
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B // R,),
        in_specs=in_specs,
        out_specs=rows_of(NH, Dh),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, Dh), q.dtype),
        # the ring's cursor and its fetches in flight pass from block
        # to block: the grid runs in order (nothing lost on one-core v5e)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*scalars, *operands)


def paged_block_attention(
    q: jax.Array,          # [B, Bk, NH, Dh]: the block's queries
    k_pages: jax.Array,    # [L, NP, PS, KVH*Dh]
    v_pages: jax.Array,
    layer: jax.Array,
    page_table: jax.Array, # [B, MP]
    past_len: jax.Array,   # [B]: tokens in the pages, a multiple of Bk
    k_blk: jax.Array,      # [B, Bk, KVH, Dh]: the block's own K (post-RoPE)
    v_blk: jax.Array,
    win_k: Optional[jax.Array] = None,   # [B, W, KVH*Dh]: earlier blocks
    win_v: Optional[jax.Array] = None,   # of the window, not yet in pages
    win_len: Optional[jax.Array] = None,  # scalar: their valid slots
    *,
    interpret: bool = False,
    rows: Optional[int] = None,
) -> jax.Array:
    """The BLOCK form of ``paged_decode_attention``, ``[B, Bk, NH, Dh]``:
    one block of a model that generates by blocks (``ModelConfig.
    block_length``), whose ``Bk`` queries a row ALL see the row's pages,
    the window's earlier blocks and all ``Bk`` current keys. That is the
    kernel's own shape and no second kernel: it already holds keys that
    every query sees and that are not in the pool (``win_k`` / ``win_v``
    / ``win_len``) beside ``k_cur``, and it already lays a KV head's
    group of query heads side by side on the sublanes. So the block's
    queries fold beside the group (``Bk x G`` query rows a KV head a
    page, against ``G``: 32 against 8 at 32 heads over 4, a fuller MXU
    tile for the same fetched page), the block's first ``Bk - 1`` keys
    go into the window's buffer behind the earlier blocks and its last
    is ``k_cur``. A row's pages are fetched once for the ``Bk``
    positions. No window and no sink: the caller gates
    (ops/attention.chunk_attention)."""
    B, Bk, NH, Dh = q.shape
    KVH = k_blk.shape[2]
    G = NH // KVH
    KD = KVH * Dh
    # [B, Bk, KVH, G, Dh] -> [B, KVH, Bk, G, Dh]: row n of the folded
    # heads belongs to KV head n // (Bk * G), as the kernel's
    # block-diagonal queries want it
    folded = q.reshape(B, Bk, KVH, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, KVH * Bk * G, Dh
    )
    if win_k is None:
        win_k = jnp.zeros((B, Bk, KD), k_blk.dtype)
        win_v = jnp.zeros((B, Bk, KD), v_blk.dtype)
        win_len = jnp.int32(0)
    win_len = jnp.asarray(win_len, jnp.int32)
    # the block's first Bk - 1 keys behind the window's valid slots
    # (W >= win_len + Bk: the window's buffer has this block's place)
    head_k = k_blk[:, : Bk - 1].reshape(B, Bk - 1, KD).astype(win_k.dtype)
    head_v = v_blk[:, : Bk - 1].reshape(B, Bk - 1, KD).astype(win_v.dtype)
    win_k = jax.lax.dynamic_update_slice(win_k, head_k, (0, win_len, 0))
    win_v = jax.lax.dynamic_update_slice(win_v, head_v, (0, win_len, 0))
    out = paged_decode_attention(
        folded, k_pages, v_pages, layer, page_table, past_len,
        k_blk[:, Bk - 1], v_blk[:, Bk - 1], jnp.int32(0),
        win_k=win_k, win_v=win_v, win_len=win_len + (Bk - 1),
        interpret=interpret, rows=rows,
    )
    return out.reshape(B, KVH, Bk, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Bk, NH, Dh
    )
