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
- Per-layer sliding windows (Gemma3 / gpt-oss alternating) arrive as a
  *dynamic* scalar-prefetch operand so one compiled kernel serves every
  layer of the model's ``lax.scan``: fully-out-of-window key blocks are
  skipped dynamically, the diagonal block is never skippable, and partial
  blocks are masked elementwise.
- gpt-oss attention sinks join the softmax denominator at finalization
  (a per-head logit with no value row — same semantics as
  ops/attention.py's jnp path).
- A learned selection (GLM-5's indexer: ops/sparse_attention.py) arrives
  as ONE more operand, ``keep`` ``[B, T, T]`` int8 shared by every head,
  fetched a ``[BQ, BK]`` tile a grid step and ANDed into the causal and
  window tests. Blocks are still skipped by causality alone and the
  diagonal block is still the last. Without it the program has no such
  operand: every other caller's is what it was.

Contract: self-attention over a chunk with NO past — query/key positions
are ``[0, T)`` (the runner's bucketed prefill and the embed path both
guarantee this; chunked long-prompt prefill carries paged past and takes
the paged/XLA path instead). Padding rows/tails (``t >= valid_len``) are
computed-and-discarded by the caller exactly as in the jnp path: a padded
query only ever attends causally, so every *used* output position
(t < valid_len) sees only real keys.

All math float32; outputs cast back to the query dtype.
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

BLOCK_Q = 128
BLOCK_K = 128
# scratch is [G, BQ, *] in float32; cap G so VMEM stays bounded. At G = 16
# (32 query heads over 2 K/V heads) and Dh = 128: m and l 2 x 16 x 128 x
# 128 x 4 B = 2 MB, acc 16 x 128 x 128 x 4 B = 1 MB, the double-buffered
# bf16 q and out blocks 4 x 0.5 MB, K and V blocks 128 KB: 5.2 MB of the
# 16 MB a v5e core's kernel may take
MAX_GROUP = 16
# what a call under a selection (``keep``) asks for in place of the 16 MiB
# a v5e kernel may take unasked (of 128): at blocks of 1,024 and heads of
# 256 the kernel stood at 16 MiB less a little, and the selection's int8
# tile, double-buffered, is 2 MiB more (17.9 MB: refused by 1.9)
KEEP_VMEM_BYTES = 32 << 20


def _flash_kernel(
    # scalar prefetch
    window_ref,       # [1] int32 (0 = full attention)
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
    native: bool = False,
    block_length: int = 1,
):
    *keep_ref, out_ref, m_ref, l_ref, acc_ref = refs
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    BQ = q_ref.shape[3]
    BK = k_ref.shape[2]
    q0 = qb * BQ
    k0 = kb * BK
    win = window_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip: strictly-above-diagonal (causal) or fully below
    # the sliding window. The diagonal block (k0 == q0) satisfies neither
    # condition, so every query row always executes at least one block.
    causal_skip = k0 > q0 + BQ - 1
    window_skip = jnp.logical_and(win > 0, k0 + BK - 1 <= q0 - win)

    @pl.when(jnp.logical_not(jnp.logical_or(causal_skip, window_skip)))
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
        # windowless (win <= 0) ORed in — Mosaic cannot legalize
        # arith.select on i1 vectors (same workaround as pallas_paged)
        ok = jnp.logical_and(
            ok, jnp.logical_or(qpos - kpos < win, win <= 0)
        )
        if keep_ref:
            ok = jnp.logical_and(ok, keep_ref[0][0].astype(jnp.int32) != 0)
        # ``native``: the operands reach the MXU in the dtype they
        # have (float32 accumulation), not up-cast first
        cast = (lambda x: x) if native else (lambda x: x.astype(jnp.float32))
        k = cast(k_ref[0, 0])                          # [BK, Dh]
        v = cast(v_ref[0, 0])                          # [BK, Dv]
        for g in range(groups):  # static unroll over heads in the group
            q = cast(q_ref[0, 0, g])                   # [BQ, Dh]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # [BQ, BK]
            s = jnp.where(ok, s, NEG_INF)

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
    # block (everything past it is causally skipped) — finalize here.
    @pl.when(k0 == q0)
    def _finalize():
        for g in range(groups):
            sink = sink_ref[0, g, 0]                   # scalar f32
            m_prev = m_ref[g, :, 0]
            m_new = jnp.maximum(m_prev, sink)
            alpha = jnp.exp(m_prev - m_new)
            # the sink contributes a probability-mass column only
            l = l_ref[g, :, 0] * alpha + jnp.exp(sink - m_new)
            out = acc_ref[g] * alpha[:, None] / jnp.maximum(l, 1e-30)[:, None]
            out_ref[0, 0, g] = out.astype(out_ref.dtype)


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
    static_argnames=("interpret", "scale", "native", "block", "block_length"),
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
    # the operands as they are to the MXU (``_flash_kernel``), and the
    # side of the square query and key blocks, where not BLOCK_Q: at one
    # query head a K/V head a block of 128 is two small products a grid
    # step, and the step's own cost leads
    native: bool = False,
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
) -> jax.Array:
    """Returns [B, T, NH, Dv] causal self-attention over the chunk."""
    lowering.record_kernel(
        "flash_prefill", interpret=interpret, heads=q.shape[2]
    )
    B, T, NH, Dh = q.shape
    if block_length & (block_length - 1) or (block or BLOCK_Q) % block_length:
        raise ValueError(
            f"block_length {block_length}: a power of two that divides the "
            f"kernel's tile of {block or BLOCK_Q}"
        )
    KVH = k.shape[2]
    Dv = v.shape[-1]
    G = NH // KVH
    scale = Dh ** -0.5 if scale is None else scale
    BQ = BK = block
    if block is None:
        BQ, BK = BLOCK_Q, BLOCK_K
    nQ = T // BQ
    nK = T // BK

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
    win = (
        jnp.zeros((1,), jnp.int32)
        if window is None
        else jnp.asarray(window, jnp.int32).reshape(1)
    )

    kernel = functools.partial(
        _flash_kernel, groups=G, scale=scale, native=native,
        block_length=block_length,
    )
    operands = [win, qh, kh, vh, sink_g]

    def key_block(kb, qb):
        # under a selection a key block over the diagonal, which is
        # skipped, names the diagonal's tiles again and nothing is
        # fetched for it (8,192 tokens, blocks of 1,024, 64 heads of
        # 256: 25.4 -> 23.5 ms; PERF.md section 6, PR 47)
        return kb if keep is None else jnp.minimum(kb, qb)

    in_specs = [
        pl.BlockSpec(
            (1, 1, G, BQ, Dh),
            lambda b, h, qb, kb, win: (b, h, 0, qb, 0),
        ),
        pl.BlockSpec(
            (1, 1, BK, Dh),
            lambda b, h, qb, kb, win: (b, h, key_block(kb, qb), 0),
        ),
        pl.BlockSpec(
            (1, 1, BK, Dv),
            lambda b, h, qb, kb, win: (b, h, key_block(kb, qb), 0),
        ),
        pl.BlockSpec(
            (1, G, 128), lambda b, h, qb, kb, win: (h, 0, 0)
        ),
    ]
    limits = {}
    if keep is not None:
        operands.append(keep)
        in_specs.append(pl.BlockSpec(
            (1, BQ, BK),
            lambda b, h, qb, kb, win: (b, qb, key_block(kb, qb)),
        ))
        limits = dict(vmem_limit_bytes=KEEP_VMEM_BYTES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, nQ, nK),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, G, BQ, Dv),
            lambda b, h, qb, kb, win: (b, h, 0, qb, 0),
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
