"""Flash (blockwise, online-softmax) causal prefill attention in Pallas.

The prefill hot path (SURVEY §2.3 row 1, §7.2 step 4). The reference's
whole value proposition is batch throughput (/root/reference/README.md:36-38)
and classify-style jobs are prefill-dominated, so prefill must not
materialize the O(T^2) score matrix the fused-XLA fallback builds.

Design (TPU-first):

- Layout is head-major: q ``[B, KVH, G, T, Dh]``, k/v ``[B, KVH, T, Dh]``
  so one grid step owns one (batch row, KV head) pair and the MXU sees
  ``[BQ, Dh] x [BK, Dh]^T`` tiles per query-head-in-group.
- Grid ``(B, KVH, nQ, nK)``; the key-block axis is innermost and
  sequential ("arbitrary"), carrying running ``(m, l, acc)`` per grouped
  query head in VMEM scratch — classic flash online softmax.
- Causality is exploited at block granularity: key blocks strictly above
  the diagonal are skipped (``pl.when``), so work is ~half of the full
  rectangle; the output is finalized and written at the diagonal block,
  which under causal masking is always the last contributing key block.
- gpt-oss attention sinks join the softmax denominator at finalization
  (a per-head logit with no value row — same semantics as
  ops/attention.py's jnp path).
- A learned selection (GLM-5's indexer: ops/sparse_attention.py) arrives
  as ONE more operand, ``keep`` ``[B, T, T]`` int8 shared by every head,
  fetched a ``[BQ, BK]`` tile a grid step and ANDed into the causal and
  window tests. Blocks are still skipped by causality alone and the
  diagonal block is still the last. Without it the program has no such
  operand: every other caller's is what it was.

Two kinds of caller, one body. A LATENT caller (``block=``:
ops/attention.latent_flash, one query head a K/V head, heads zero-padded
to whole lane tiles) brings its own square blocks and gets the program
it has had since PR 42 and 47: every tile of the causal half is a grid
step, the window is a dynamic scalar (0), the bucket's padding is
computed (tests/test_flash_prefill_aot.py holds its jaxpr to the
letter). A GQA caller (``chunk_attention``: ``G`` query heads a K/V
head) gets a TILE SCHEDULE that follows what the call can observe
(PERF.md section 6, PR 62):

- **the tile**: square blocks of the largest side of ``GQA_BLOCKS``
  (512, 256, 128) that divides the chunk and whose step fits VMEM
  (``gqa_tiles``, ``gqa_vmem_bytes``; the call asks ``GQA_VMEM_BYTES``).
  A step is ``G`` pairs of products; at 128 x 128 the step's own cost
  led, and K and V streamed from HBM 64 times a head at 8,192 tokens;
- **the walk**, the key blocks a query block visits. ``live_window``
  (static: a layer KIND's window, or a constant the caller holds) makes
  the key axis of the grid the window's blocks, ``ceil`` of window over
  tile plus one, starting at the query block's first in-window key
  block: ``walk=window``. No window: the blocks up to the diagonal,
  ``walk=causal``. A window that is a runtime scalar (a homogeneous
  ``lax.scan``'s per-layer window: Gemma3 / gpt-oss alternating, and
  every scan model's 0) walks the causal half and skips a tile outside
  the window a step at a time, as it always did: ``walk=dynamic``.
  ``valid_len`` ``[B]`` rides behind the window in the scalar prefetch:
  a key block at or past a row's end is not visited, a query tile wholly
  there does no product and is written ZERO (later layers read the
  padded rows; ops/pallas_chunk.py's padding tile comes out zero for the
  same reason). A step the kernel skips names the last needed key block
  again in its index map, so nothing is fetched for it;
- **the statistics** ``(m, l)`` stay ``[BQ, 128]`` with every lane a
  copy from scratch to scratch: no vector of one value a row is made
  (each cost a relayout between sublanes and lanes, and was half the
  body's time at every tile).

Contract: self-attention over a chunk with NO past — query/key positions
are ``[0, T)`` (the runner's bucketed prefill and the embed path both
guarantee this; chunked long-prompt prefill carries paged past and takes
ops/pallas_chunk.py instead). A padded query (``t >= valid_len``) inside
a tile that holds valid ones is computed and discarded by the caller as
in the jnp path: it only ever attends causally, so every *used* output
position (t < valid_len) sees only real keys. A padding TILE of a GQA
call is zero.

Operands reach the MXU in the dtype they have (bfloat16 for every
configuration of the benchmark) with float32 accumulation; scores,
masks, the softmax and the running ``(m, l, acc)`` are float32, and the
probabilities are rounded to the values' dtype for their product: what
ops/pallas_chunk.py gives a chunked prefill of the same model. Outputs
are cast back to the query dtype. Float32 operands stay float32.
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

#: the smallest tile, and the gate's (``flash_prefill_supported``)
BLOCK_Q = 128
BLOCK_K = 128
#: sides a GQA call's square query and key blocks are chosen among
#: (``gqa_tiles``): the largest that divides the chunk and fits. Past
#: 512 nothing is gained (a step of 512 x 512 a head is its arithmetic)
#: and a window's walk computes more of what its mask drops
GQA_BLOCKS = (512, 256, 128)
# scratch is [G, BQ, *] in float32; cap G so VMEM stays bounded. At G = 16
# (32 query heads over 2 K/V heads), Dh = 128 and the smallest tile: m and
# l 2 x 16 x 128 x 128 x 4 B = 2 MB, acc 16 x 128 x 128 x 4 B = 1 MB, the
# double-buffered bf16 q and out blocks 4 x 0.5 MB, K and V blocks 128 KB:
# 5.2 MB; ``gqa_tiles`` grows the tile from there while the same sum
# (``gqa_vmem_bytes``) stays under ``GQA_VMEM_BUDGET``
MAX_GROUP = 16
# what a call under a selection (``keep``) asks for in place of the 16 MiB
# a v5e kernel may take unasked (of 128): at blocks of 1,024 and heads of
# 256 the kernel stood at 16 MiB less a little, and the selection's int8
# tile, double-buffered, is 2 MiB more (17.9 MB: refused by 1.9)
KEEP_VMEM_BYTES = 32 << 20
#: what a GQA call asks for, and what its tile may take of it by
#: ``gqa_vmem_bytes`` (the compiler's own temporaries have the rest)
GQA_VMEM_BYTES = 48 << 20
GQA_VMEM_BUDGET = 28 << 20


def _flash_kernel(
    # scalar prefetch
    scal_ref,         # [1] int32: the dynamic window (0 = full attention);
    #                   [1 + B] where the call brings ``valid_len`` behind it
    # operands
    q_ref,            # [1, 1, G, BQ, Dh]
    k_ref,            # [1, 1, BK, Dh]
    v_ref,            # [1, 1, BK, Dh]
    sink_ref,         # [1, G, 128] f32 (NEG_INF rows when no sink)
    # [keep_ref [1, BQ, BK] int8, where the caller brings a selection,]
    # then the output out_ref [1, 1, G, BQ, Dh] and the scratch m_ref,
    # l_ref [G, BQ, 128] f32 and acc_ref [G, BQ, Dh] f32
    *refs,
    groups: int,
    scale: float,
    upcast: bool = False,
    block_length: int = 1,
    # the walk (``flash_prefill``): a STATIC window (the key axis starts
    # at the query block's first in-window key block), whether the mask
    # reads the dynamic scalar, and whether ``valid_len`` came
    window: int = 0,
    dynamic: bool = True,
    valid: bool = False,
    # a GQA call's: the running ``(m, l)`` lane-replicated throughout
    replicated: bool = False,
    has_sink: bool = True,
):
    *keep_ref, out_ref, m_ref, l_ref, acc_ref = refs
    qb = pl.program_id(2)
    step = kb = pl.program_id(3)
    BQ = q_ref.shape[3]
    BK = k_ref.shape[2]
    q0 = qb * BQ
    if window:
        kb = step + _first_key_block(qb, BQ, BK, window)
    k0 = kb * BK
    win = scal_ref[0] if dynamic else None

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip: strictly-above-diagonal (causal) or fully below
    # the sliding window. The diagonal block (k0 == q0) satisfies neither
    # condition, so every query row always executes at least one block.
    skip = k0 > q0 + BQ - 1
    if dynamic:
        skip = jnp.logical_or(
            skip, jnp.logical_and(win > 0, k0 + BK - 1 <= q0 - win)
        )
    if valid:
        # a key block at or past the row's end, and every block of a
        # query tile there (a bucket's padding)
        vl = scal_ref[1 + pl.program_id(0)]
        skip = jnp.logical_or(skip, jnp.logical_or(k0 >= vl, q0 >= vl))

    @pl.when(jnp.logical_not(skip))
    def _accumulate():
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        if block_length > 1:
            # causal by blocks: a query sees its own block whole. The
            # block divides the tile, so only the diagonal tile's mask
            # differs from the causal one (``flash_prefill``)
            ok = kpos <= jnp.bitwise_or(qpos, block_length - 1)
        else:
            ok = kpos <= qpos
        if dynamic:
            # windowless (win <= 0) ORed in — Mosaic cannot legalize
            # arith.select on i1 vectors (same workaround as pallas_paged)
            ok = jnp.logical_and(
                ok, jnp.logical_or(qpos - kpos < win, win <= 0)
            )
        elif window:
            ok = jnp.logical_and(ok, qpos - kpos < window)
        if keep_ref:
            ok = jnp.logical_and(ok, keep_ref[0][0].astype(jnp.int32) != 0)
        # the operands reach the MXU in the dtype they have (float32
        # accumulation); ``upcast`` is what the body did for a GQA call
        # before (benchmarks/flash_prefill_ab.py prices it)
        cast = (lambda x: x.astype(jnp.float32)) if upcast else (lambda x: x)
        k = cast(k_ref[0, 0])                          # [BK, Dh]
        v = cast(v_ref[0, 0])                          # [BK, Dv]
        for g in range(groups):  # static unroll over heads in the group
            q = cast(q_ref[0, 0, g])                   # [BQ, Dh]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # [BQ, BK]
            s = jnp.where(ok, s, NEG_INF)

            if replicated:
                # the running statistics stay [BQ, 128], every lane a
                # copy: no vector of one value a row is ever made, which
                # costs a relayout between sublanes and lanes each way
                m_prev = m_ref[g]                      # [BQ, 128]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=1, keepdims=True)
                )
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - pltpu.repeat(m_new, BK // 128, axis=1))
                if keep_ref:
                    p = jnp.where(ok, p, 0.0)
                l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
                m_ref[g] = m_new
                acc_ref[g] = acc_ref[g] * _lanes(alpha, acc_ref.shape[2]) + (
                    jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )
                continue
            m_prev = m_ref[g, :, 0]                    # [BQ]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)            # [BQ]
            p = jnp.exp(s - m_new[:, None])            # [BQ, BK]
            if keep_ref:
                # a query may keep NO key of a block, nor of any before
                # it: m is still NEG_INF there and exp(s - m) is 1 on
                # every masked lane
                p = jnp.where(ok, p, 0.0)
            l_new = l_ref[g, :, 0] * alpha + jnp.sum(p, axis=1)
            acc_ref[g] = acc_ref[g] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[g] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])

    # The diagonal block is the last contributing key block for this query
    # block (everything past it is causally skipped) — finalize here. With
    # ``valid_len`` the last is the row's last key block where that comes
    # first, and a query tile wholly behind the row's end, which no step
    # above touched, is written ZERO: later layers read the padded rows
    if valid:
        last = jnp.minimum(_diagonal_key_block(qb, BQ, BK), (vl - 1) // BK)
        done = jnp.logical_and(kb == last, q0 < vl)

        @pl.when(jnp.logical_and(step == 0, q0 >= vl))
        def _padding():
            out_ref[...] = jnp.zeros_like(out_ref)
    elif BQ == BK:
        done = k0 == q0
    else:
        done = kb == _diagonal_key_block(qb, BQ, BK)

    @pl.when(done)
    def _finalize():
        for g in range(groups):
            sink = sink_ref[0, g, 0]                   # scalar f32
            if replicated:
                l, alpha = l_ref[g], 1.0
                if has_sink:
                    m_prev = m_ref[g]
                    m_new = jnp.maximum(m_prev, sink)
                    alpha = jnp.exp(m_prev - m_new)
                    l = l * alpha + jnp.exp(sink - m_new)
                out = acc_ref[g] * _lanes(
                    alpha / jnp.maximum(l, 1e-30), acc_ref.shape[2]
                )
                out_ref[0, 0, g] = out.astype(out_ref.dtype)
                continue
            m_prev = m_ref[g, :, 0]
            m_new = jnp.maximum(m_prev, sink)
            alpha = jnp.exp(m_prev - m_new)
            # the sink contributes a probability-mass column only
            l = l_ref[g, :, 0] * alpha + jnp.exp(sink - m_new)
            out = acc_ref[g] * alpha[:, None] / jnp.maximum(l, 1e-30)[:, None]
            out_ref[0, 0, g] = out.astype(out_ref.dtype)


def _lanes(x, width: int):
    """``x`` ``[BQ, 128]``, every lane a copy, as wide as ``width``."""
    return x if width == 128 else pltpu.repeat(x, width // 128, axis=1)


def _first_key_block(qb, BQ: int, BK: int, window: int):
    """The key block that holds the first key in ``window`` of query block
    ``qb``'s first query (a traced index or a Python one)."""
    first = qb * BQ - (window - 1)
    if isinstance(first, int):
        return max(first, 0) // BK
    return jnp.maximum(first, 0) // BK


def _diagonal_key_block(qb, BQ: int, BK: int):
    """The key block that holds the last query of query block ``qb``."""
    return qb if BQ == BK else (qb * BQ + BQ - 1) // BK


def key_steps(T: int, BQ: int, BK: int, window: int = 0) -> int:
    """The key axis of the grid: every key block of the chunk, or under
    a static ``window`` the most blocks that a query block's window and
    diagonal span."""
    if not window:
        return T // BK
    return max(
        _diagonal_key_block(qb, BQ, BK) + 1
        - _first_key_block(qb, BQ, BK, window)
        for qb in range(T // BQ)
    )


def gqa_vmem_bytes(G: int, BQ: int, BK: int, Dh: int, Dv: int, io_bytes: int) -> int:
    """VMEM a grid step of a GQA call takes, as the shapes tell it: the
    scratch a head, the pipeline's two buffers of every block, and ONE
    head's float32 scores four times over (the scores, the mask, the
    probabilities and their rounded copy)."""
    scratch = G * BQ * (128 + 128 + Dv) * 4
    blocks = 2 * G * BQ * (Dh + Dv) * io_bytes + 2 * BK * (Dh + Dv) * io_bytes
    return scratch + blocks + 4 * BQ * BK * 4


def gqa_tiles(
    T: int, G: int, Dh: int, Dv: int, *, io_bytes: int = 2, block_length: int = 1,
) -> tuple:
    """``(BQ, BK)`` of a GQA call over a chunk of ``T``: the largest side
    of ``GQA_BLOCKS`` that divides the chunk and whose step fits
    ``GQA_VMEM_BUDGET`` (``gqa_vmem_bytes``). A grid step is ``G`` pairs
    of ``[BQ, Dh] x [BK, Dh]`` products: at 128 x 128 the step's own cost
    led (PERF.md section 6, PR 62), and K and V stream from HBM once a
    QUERY block. The body itself takes ``BQ != BK`` (the builder's
    ``tiles``); no shape measured gained from it."""
    for side in GQA_BLOCKS:
        if T % side or side % block_length:
            continue
        if side == BLOCK_Q or (
            gqa_vmem_bytes(G, side, side, Dh, Dv, io_bytes) <= GQA_VMEM_BUDGET
        ):
            return side, side
    raise ValueError(f"no tile of {GQA_BLOCKS} divides a chunk of {T}")


def flash_prefill_supported(
    q: jax.Array, k: jax.Array, window, sink, block: int = BLOCK_Q
) -> bool:
    """Static shape gate for the compiled TPU path. window/sink are
    dynamic operands of the kernel, so they never gate."""
    B, T, NH, Dh = q.shape
    KVH = k.shape[2]
    if NH % KVH:
        return False
    G = NH // KVH
    return (
        T >= block
        and T % block == 0
        and Dh % 128 == 0
        and G <= MAX_GROUP
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "scale", "block", "block_length", "live_window",
        "tiles", "before",
    ),
)
def flash_prefill(
    q: jax.Array,                    # [B, T, NH, Dh]
    k: jax.Array,                    # [B, T, KVH, Dh] (post-RoPE)
    v: jax.Array,                    # [B, T, KVH, Dv]; Dv = Dh but for
    #                                  a latent layer's expanded heads
    *,
    window: Optional[jax.Array] = None,   # scalar int32; 0/None => full
    sink: Optional[jax.Array] = None,     # [NH] logits or None
    interpret: bool = False,
    # the softmax scale where it is not 1/sqrt(Dh): heads zero-padded
    # to the kernel's one head size (ops/attention.latent_attention)
    scale: Optional[float] = None,
    # a LATENT caller's square query and key blocks (ops/attention.
    # latent_flash: one query head a K/V head). Its program is the one
    # it was: every tile of the causal half walked, the window a dynamic
    # scalar, no ``valid_len``
    block: Optional[int] = None,
    # [B, T, T] int8, 1 where this query may attend to this key: a
    # selection shared by every head, a subset of the causal triangle
    # (ops/sparse_attention.masked_attention). A query that keeps
    # nothing comes out zero
    keep: Optional[jax.Array] = None,
    # the mask is causal by blocks of this many positions
    # (``ModelConfig.block_length``), a power of two that divides the
    # kernel's tile: the tiles the causal walk skips are the tiles this
    # one skips, and the diagonal tile's mask is ``k <= q | (Bk - 1)``.
    # The chunk's padding has to start at a block's edge (a row's valid
    # length a multiple of the block), or a valid query would see it
    block_length: int = 1,
    # a GQA call's walk. ``valid_len`` [B]: a row's real tokens; key
    # tiles past them are not visited and a query tile wholly past them
    # comes out ZERO. ``live_window`` (static): the layer's window where
    # the caller knows it before tracing (a "swa" layer KIND's): the key
    # axis of the grid is the window's blocks and no more, and ``window``
    # is not read
    valid_len: Optional[jax.Array] = None,
    live_window: int = 0,
    # the builder's (benchmarks/flash_prefill_ab.py): the tile in place
    # of ``gqa_tiles``'s, and what a GQA call keeps as the body had it
    # before PR 62: "operands" (up-cast to float32 first) and "stats"
    # (the running ``(m, l)`` read back as one value a row)
    tiles: Optional[tuple] = None,
    before: tuple = (),
) -> jax.Array:
    """Returns [B, T, NH, Dv] causal self-attention over the chunk."""
    B, T, NH, Dh = q.shape
    KVH = k.shape[2]
    Dv = v.shape[-1]
    G = NH // KVH
    latent = block is not None
    if latent:
        BQ = BK = block
    else:
        BQ, BK = tiles or gqa_tiles(
            T, G, Dh, Dv, io_bytes=q.dtype.itemsize, block_length=block_length
        )
    if block_length & (block_length - 1) or BK % block_length:
        raise ValueError(
            f"block_length {block_length}: a power of two that divides the "
            f"kernel's tile of {BK}"
        )
    # the window a static bound only where it cuts the walk
    window_walk = 0 if latent or live_window >= T else live_window
    dynamic = latent or (window is not None and not live_window)
    lowering.record_kernel(
        "flash_prefill", interpret=interpret, heads=NH,
        form=None if latent else dict(
            tile=f"{BQ}x{BK}",
            walk="window" if window_walk else (
                "dynamic" if dynamic else "causal"
            ),
            operands="float32" if "operands" in before else str(q.dtype),
        ),
    )
    scale = Dh ** -0.5 if scale is None else scale
    nQ = T // BQ
    nK = key_steps(T, BQ, BK, window_walk)

    # head-major layout: [B, KVH, G, T, Dh] / [B, KVH, T, Dh]
    qh = q.reshape(B, T, KVH, G, Dh).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    if sink is None:
        sink_g = jnp.full((KVH, G, 128), NEG_INF, jnp.float32)
    else:
        sink_g = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(KVH, G, 1), (KVH, G, 128)
        )
    scal = (
        jnp.zeros((1,), jnp.int32)
        if window is None or not dynamic
        else jnp.asarray(window, jnp.int32).reshape(1)
    )
    valid = valid_len is not None and not latent
    if valid:
        scal = jnp.concatenate([scal, valid_len.astype(jnp.int32)])

    kernel = functools.partial(
        _flash_kernel, groups=G, scale=scale, upcast="operands" in before,
        block_length=block_length, window=window_walk, dynamic=dynamic,
        valid=valid, replicated=not latent and "stats" not in before,
        has_sink=sink is not None,
    )
    operands = [scal, qh, kh, vh, sink_g]

    def key_block(b, kb, qb, scal):
        if latent:
            # under a selection a key block over the diagonal, which is
            # skipped, names the diagonal's tiles again and nothing is
            # fetched for it (8,192 tokens, blocks of 1,024, 64 heads of
            # 256: 25.4 -> 23.5 ms; PERF.md section 6, PR 47)
            return kb if keep is None else jnp.minimum(kb, qb)
        # a GQA call: the walk starts at the window's first block, and a
        # step the kernel skips (over the diagonal, past the row's end)
        # names the last block again: nothing is fetched for it
        if window_walk:
            kb = kb + _first_key_block(qb, BQ, BK, window_walk)
        last = _diagonal_key_block(qb, BQ, BK)
        if valid:
            last = jnp.minimum(last, jnp.maximum(scal[1 + b] - 1, 0) // BK)
        return jnp.minimum(kb, last)

    in_specs = [
        pl.BlockSpec(
            (1, 1, G, BQ, Dh),
            lambda b, h, qb, kb, scal: (b, h, 0, qb, 0),
        ),
        pl.BlockSpec(
            (1, 1, BK, Dh),
            lambda b, h, qb, kb, scal: (b, h, key_block(b, kb, qb, scal), 0),
        ),
        pl.BlockSpec(
            (1, 1, BK, Dv),
            lambda b, h, qb, kb, scal: (b, h, key_block(b, kb, qb, scal), 0),
        ),
        pl.BlockSpec(
            (1, G, 128), lambda b, h, qb, kb, scal: (h, 0, 0)
        ),
    ]
    limits = {} if latent else dict(vmem_limit_bytes=GQA_VMEM_BYTES)
    if keep is not None:
        operands.append(keep)
        in_specs.append(pl.BlockSpec(
            (1, BQ, BK),
            lambda b, h, qb, kb, scal: (b, qb, key_block(b, kb, qb, scal)),
        ))
        limits = dict(vmem_limit_bytes=KEEP_VMEM_BYTES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, nQ, nK),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, G, BQ, Dv),
            lambda b, h, qb, kb, scal: (b, h, 0, qb, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((G, BQ, 128), jnp.float32),
            pltpu.VMEM((G, BQ, 128), jnp.float32),
            pltpu.VMEM((G, BQ, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, T, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
            **limits,
        ),
        interpret=interpret,
    )(*operands)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, NH, Dv)
