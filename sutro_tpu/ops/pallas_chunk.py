"""Pallas TPU kernel: a chunk of several tokens over a paged past.

A verify forward (``[64, 17]``: the forced tokens of a schema's
scaffold), the suffix of a job's rows over its shared prefix's pages
(``[8, 256]``) and a chunk of a chunked prefill all attend ``T > 1``
queries a row to the row's PAGED past and to the chunk's own ``T`` keys
under the causal mask. The XLA form (ops/attention.chunk_attention)
gathers the row's whole table for that, ``[B, MP x PS, KD]`` whatever
the row holds, casts it to float32 and puts ``[B, KVH, G, T, S]``
float32 scores through HBM. This kernel reads the pages where they lie.

Design (one kernel, one tile rule for both shapes; the second form: the
first gave every page of a key step a BlockSpec of its own over the pool
and the key steps a grid axis, and paid ~1.2 us of pipeline bookkeeping
for eleven operands a grid step, needed or not: 14 of the verify
shape's 39 ms a layer stack with no arithmetic at all, PERF.md §6
PR 60):

- grid ``(B, nQ)``: a grid step is one query tile of one row. The
  pipeline brings the tile's ``q``, the row's own ``k`` / ``v`` and the
  output, four blocks; the carry ``(m, l, acc)`` a KV head lives in VMEM
  scratch for the step;
- **the past, in place**: the operands are the WHOLE stacked pools
  ``[L, NP, PS, KVH*Dh]`` (``memory_space=ANY``, HBM-resident); a key
  step is ``step_pages`` pages of the row (256 keys at pages of 64),
  fetched through the row's table as ``pool.at[layer, page]``, one
  contiguous DMA a page, into one of two VMEM slots where they lie one
  under the other: the next step's pages land while this step's are
  read. No gather, no per-layer slice;
- **bytes and arithmetic follow the row**: the loop over key steps runs
  ``ceil(ceil(past_len / PS) / step_pages)`` times, what the row holds
  and not what its table could. Inside the last step a slot past the
  row's pages names its last page again and is masked (every step is
  the same copies and the same waits): at most ``step_pages - 1`` pages
  a row fetched twice;
- **the fetches do not start from nothing a grid step**: a grid step
  finds its first pages already in flight, started by the grid step
  before it once its own loop had read both slots, so they land under
  that step's own keys and its finalize. The grid therefore runs in
  order (``arbitrary``; nothing lost on one-core v5e);
- **the needed FLOPs and no more**: a page arrives whole (the fused
  ``KVH*Dh`` axis on the lanes); the heads' lane blocks are laid a head
  apart and the two products are BATCHED over the KV heads
  (``[KVH, TQ*G, Dh] x [KVH, keys, Dh]``), the softmax one chain over
  ``[KVH, TQ*G, keys]``: eight heads' chains one after the other left
  each other's latencies unfilled (38.8 -> 31.6 ms a layer stack at the
  verify shape in the first form). The decode kernel's block-diagonal
  form costs KVH times the FLOPs, which a ``T = 1`` step hides behind
  HBM and a chunk of 256 cannot;
- **the queries fold beside their group**: ``q`` and the output stay
  ``[B, T, NH*Dh]`` in HBM, no transpose either way; a grid step's
  ``[TQ, NH*Dh]`` block gives a KV head's ``G`` query heads as ``G``
  lane blocks, laid one under the other in VMEM (``TQ*G`` rows: 128 at
  the verify shape, 256 at a prefill chunk's), so a fetched page serves
  the group's heads in one product;
- **the chunk's own keys** come last, blocks of up to 256 of the
  chunk's ``[B, T, KD]`` under the causal mask and ``valid_len``; a
  block wholly above the tile's diagonal or wholly past ``valid_len``
  is skipped, and so is every step of a query tile wholly behind
  ``valid_len`` (a bucket's padding, which nobody reads);
- the layer, the table, ``past_len``, ``valid_len`` and a layer's
  sliding window are dynamic (scalar prefetch): one compiled kernel
  serves every layer of the ``lax.scan``.

Operands reach the MXU in the dtype they have; accumulation, masks and
the softmax are float32 and the probabilities are rounded to the values'
dtype for their product: what ``flash_prefill`` gives a whole-prompt
prefill of the same model. Every valid key of the past and of the chunk
is scored.

A query's position is ``past_len + t`` (``t`` its index in the chunk),
as every caller of a paged chunk gives it (engine/runner.py). A query
that sees no key at all (a padding row: ``past_len`` 0 and ``valid_len``
0; a bucket's padding behind a short window) comes out finite and
meaningless, as the gather's does; a padding TILE comes out zero.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lowering

NEG_INF = -1e30

#: keys a step takes: the pages of the past fetched together, and a
#: block of the chunk's own keys
KEY_TOKENS = 256
#: query rows (tokens x a KV head's group) a grid step folds together
QUERY_ROWS = 256
#: pages a step fetches at most (a DMA and a semaphore a page, K and V)
MAX_STEP_PAGES = 8
#: what a call asks for in place of the 16 MiB a v5e kernel may take
#: unasked (of 128; ops/pallas_paged.VMEM_LIMIT_BYTES), as the flash
#: kernel under a selection does: at qwen3-4b's heads a tile of 64
#: tokens and a chunk of 256 stand at 17 MB by ``chunk_vmem_bytes``
VMEM_LIMIT_BYTES = 32 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def chunk_vmem_bytes(
    TQ: int, Tp: int, BK: int, step_pages: int, NH: int, KVH: int, Dh: int,
    PS: int, *, pool_bytes: int, io_bytes: int,
) -> int:
    """VMEM a grid step takes, as the shapes tell it: the pipeline's two
    buffers of every block, the two slots of fetched pages, the carry a
    KV head, a step's operands laid a head apart and its scores."""
    KD, RQ = KVH * Dh, TQ * (NH // KVH)
    keys = max(step_pages * PS, BK)
    blocks = 2 * 2 * TQ * NH * Dh * io_bytes             # q, out
    blocks += 2 * 2 * Tp * KD * io_bytes                 # the chunk's own
    slots = 2 * 2 * step_pages * PS * KD * pool_bytes    # K and V pages
    carry = KVH * RQ * (2 * 128 + Dh) * 4                # m, l, acc
    laid = KVH * RQ * Dh * io_bytes + 2 * keys * KD * pool_bytes
    scores = 3 * KVH * RQ * keys * 4
    return blocks + slots + carry + laid + scores


def chunk_tiles(
    T: int, NH: int, KVH: int, Dh: int, PS: int,
    *, pool_bytes: int, io_bytes: int,
) -> Optional[Tuple[int, int, int, int]]:
    """``(TQ, Tp, BK, step_pages)``: the tokens of a query tile (``TQ x
    G`` rows a KV head, up to ``QUERY_ROWS``), the chunk's padded length,
    the chunk's own keys a block and the pages of the past a step; None
    where no tile fits ``VMEM_LIMIT_BYTES``. ONE rule for a verify
    forward's 17 tokens (one tile of 32, 128 rows a head at a group of
    4) and a prefill chunk's 256 (four tiles of 64)."""
    G = NH // KVH
    tq_max = max(16, 1 << (max(QUERY_ROWS // G, 1).bit_length() - 1))
    step_pages = max(1, min(KEY_TOKENS // PS, MAX_STEP_PAGES))
    while True:
        TQ = min(_round_up(T, 16), tq_max)
        if T <= KEY_TOKENS:
            Tp = BK = _round_up(T, TQ)
        else:
            Tp, BK = _round_up(T, KEY_TOKENS), KEY_TOKENS
        if chunk_vmem_bytes(
            TQ, Tp, BK, step_pages, NH, KVH, Dh, PS,
            pool_bytes=pool_bytes, io_bytes=io_bytes,
        ) <= VMEM_LIMIT_BYTES:
            return TQ, Tp, BK, step_pages
        if tq_max > 16:
            tq_max //= 2
        elif step_pages > 1:
            step_pages //= 2
        else:
            return None


def paged_chunk_supported(
    q: jax.Array,          # [B, T, NH, Dh]
    k_pages: jax.Array,    # [L, NP, PS, KVH*Dh]
    *, k_scale=None, sink=None, win_k=None, live_window: int = 0,
    kernel_mesh=None,
) -> bool:
    """The gate of the compiled TPU path, by the call's own shapes and
    operands: heads of whole lane tiles, pages of whole sublane tiles,
    tiles that fit VMEM; no int8 pages (their scales), no sink, no
    window pool (``live_window``), no fused window's buffer beside the
    chunk and no mesh (the call is not shard_mapped). A call it refuses
    takes the gather (ops/attention.chunk_attention)."""
    B, T, NH, Dh = q.shape
    PS, KD = k_pages.shape[2:]
    if (
        k_scale is not None or sink is not None or live_window
        or kernel_mesh is not None
        or (win_k is not None and win_k.shape[1] > 0)
    ):
        return False
    pool_bytes = k_pages.dtype.itemsize
    if T < 2 or Dh % 128 or KD % Dh or NH % (KD // Dh):
        return False
    if pool_bytes not in (2, 4) or PS % (32 // pool_bytes):
        return False
    return chunk_tiles(
        T, NH, KD // Dh, Dh, PS,
        pool_bytes=pool_bytes, io_bytes=q.dtype.itemsize,
    ) is not None


def _paged_chunk_kernel(
    # scalar prefetch: page_table [B*MP], past_len [B], valid_len [B],
    # layer [1], window [1] (0 = full attention)
    table_ref, past_ref, valid_ref, layer_ref, window_ref,
    q_ref,            # [1, TQ, NH*Dh]
    k_pool_ref,       # [L, NP, PS, KD], in HBM
    v_pool_ref,
    kc_ref,           # [1, Tp, KD]: the chunk's own keys
    vc_ref,
    out_ref,          # [1, TQ, NH*Dh]
    kbuf, vbuf,       # [2, X, KD]: two slots of a step's pages
    ksem, vsem,       # DMA semaphores [2, step_pages]
    qs_ref,           # [KVH, RQ, Dh]: the tile's folded queries
    m_ref, l_ref,     # [KVH, RQ, 128] f32
    acc_ref,          # [KVH, RQ, Dh] f32
    *,
    kvh: int,
    groups: int,
    max_pages_per_seq: int,
    page_size: int,
    own_block: int,
    scale: float,
):
    G, MP, PS, BK = groups, max_pages_per_seq, page_size, own_block
    b, qb = pl.program_id(0), pl.program_id(1)
    B, nQ = pl.num_programs(0), pl.num_programs(1)
    TQ = q_ref.shape[1]
    RQ = TQ * G
    Dh = acc_ref.shape[2]
    X = kbuf.shape[1]                  # keys of a step of the past
    NPG = X // PS
    Tp = kc_ref.shape[1]
    layer, win = layer_ref[0], window_ref[0]
    past, valid = past_ref[b], valid_ref[b]

    def held(row):
        return jnp.minimum((past_ref[row] + PS - 1) // PS, MP)

    def copies(slot, page_of):
        """A step's copies into ``slot``, one a page, K and V;
        ``page_of(i)`` the pool page of the step's ``i``-th."""
        out = []
        for i in range(NPG):
            page, at = page_of(i), pl.ds(i * PS, PS)
            out += [
                pltpu.make_async_copy(
                    k_pool_ref.at[layer, page], kbuf.at[slot, at],
                    ksem.at[slot, i],
                ),
                pltpu.make_async_copy(
                    v_pool_ref.at[layer, page], vbuf.at[slot, at],
                    vsem.at[slot, i],
                ),
            ]
        return out

    def fetch(row, step, slot):
        """Start ``row``'s pages ``step * NPG`` on into ``slot``. A
        page past the row's last names it again (masked below): every
        step is the same copies and the same waits."""
        last = jnp.maximum(held(row) - 1, 0)
        for dma in copies(
            slot,
            lambda i: table_ref[row * MP + jnp.minimum(step * NPG + i, last)],
        ):
            dma.start()

    # The fetches of the call are ONE sequence over the grid's steps in
    # order: a grid step finds its first pages in slot 0, started by the
    # step before it (by itself, the call's first), and starts the next
    # grid step's before its own keys and its finalize, which run under
    # them. So the grid runs in order ("arbitrary")
    @pl.when(jnp.logical_and(b == 0, qb == 0))
    def _open():
        fetch(b, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # the tile's queries folded beside their group, once a grid step:
    # a KV head's G query heads, lane blocks of the fetched tile, one
    # under the other
    for h in range(kvh):
        for g in range(G):
            qs_ref[h, pl.ds(g * TQ, TQ)] = (
                q_ref[0, :, pl.ds((h * G + g) * Dh, Dh)]
            )

    # a tile wholly behind the row's valid tokens is the bucket's
    # padding: nobody reads its output, and it fetches no further page
    live = qb * TQ < jnp.maximum(valid, 1)

    def token(cols):
        """``[RQ, cols]``: the index in the chunk of each query row's
        token. A KV head's rows lie group-major, row ``g * TQ + t``."""
        t = jax.lax.broadcasted_iota(jnp.int32, (TQ, cols), 0)
        return qb * TQ + jnp.concatenate([t] * G, axis=0)

    def heads(x):
        """``[keys, KD]`` -> ``[KVH, keys, Dh]``: a head's lane block a
        batch entry of the two products."""
        return jnp.stack([x[:, h * Dh:(h + 1) * Dh] for h in range(kvh)])

    def attend(k, v, ok):
        """Fold keys ``k`` / ``v`` ``[keys, KD]`` into every KV head's
        carry under ``ok`` ``[RQ, keys]``: the heads are the batch of
        ONE chain (product, max, exp, sum, product), whose latencies a
        head alone cannot fill."""
        qs = qs_ref[...]                                   # [KVH, RQ, Dh]
        ks, vs = heads(k).astype(qs.dtype), heads(v).astype(qs.dtype)
        s = jax.lax.dot_general(
            qs, ks, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                          # [KVH, RQ, keys]
        s = jnp.where(ok[None], s, NEG_INF)
        m_prev = m_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a query that has seen no key yet keeps m = NEG_INF and counts
        # every masked key as one; its first valid key's alpha is 0 and
        # wipes that (a query that never sees one is padding)
        p = jnp.exp(s - m_new)
        l_new = l_ref[:, :, 0:1] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def in_window(qpos, kpos):
        # windowless (win <= 0) ORed in: Mosaic cannot legalize
        # arith.select on i1 vectors (as ops/pallas_paged.py)
        return jnp.logical_or(qpos - kpos < win, win <= 0)

    # the steps of the past the tile takes: ceil(held / NPG), none for
    # padding; the loop runs once at least, for the wait on slot 0
    steps = jnp.where(live, (held(b) + NPG - 1) // NPG, 0)

    def past_step(step, _):
        slot = jax.lax.rem(step, 2)

        @pl.when(step + 1 < steps)
        def _ahead():
            fetch(b, step + 1, 1 - slot)

        # a wait reads its copy's semaphore and size: any page stands
        # for the one that was fetched
        for dma in copies(slot, lambda i: 0):
            dma.wait()

        @pl.when(step < steps)
        def _fold():
            # the step's pages lie one under the other, [X, KD]:
            # positions step * X on; a slot past the row's pages is
            # masked
            kpos = step * X + jax.lax.broadcasted_iota(jnp.int32, (RQ, X), 1)
            ok = jnp.logical_and(
                kpos < past, in_window(past + token(X), kpos)
            )
            attend(kbuf[slot], vbuf[slot], ok)

        return 0

    jax.lax.fori_loop(0, jnp.maximum(steps, 1), past_step, 0)

    # both slots have been read: the next grid step's first pages
    nxt = jnp.where(qb + 1 < nQ, b, b + 1)

    @pl.when(nxt < B)
    def _next():
        fetch(nxt, 0, 0)

    # the chunk's own keys, a block at a time: causal, and valid. A
    # block wholly over the tile's diagonal or past valid_len is skipped
    for c in range(Tp // BK):
        @pl.when(jnp.logical_and(
            live, jnp.logical_and(c * BK < (qb + 1) * TQ, c * BK < valid)
        ))
        def _own(c=c):
            j = c * BK + jax.lax.broadcasted_iota(jnp.int32, (RQ, BK), 1)
            t = token(BK)
            ok = jnp.logical_and(
                jnp.logical_and(j <= t, j < valid), in_window(t, j)
            )
            attend(
                kc_ref[0, pl.ds(c * BK, BK)], vc_ref[0, pl.ds(c * BK, BK)], ok
            )

    out = acc_ref[...] / jnp.maximum(l_ref[:, :, 0:1], 1e-30)
    for h in range(kvh):
        for g in range(G):
            out_ref[0, :, pl.ds((h * G + g) * Dh, Dh)] = (
                out[h, g * TQ:(g + 1) * TQ].astype(out_ref.dtype)
            )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_chunk_attention(
    q: jax.Array,          # [B, T, NH, Dh]: the chunk's queries
    k: jax.Array,          # [B, T, KVH, Dh]: its own K (post-RoPE)
    v: jax.Array,
    k_pages: jax.Array,    # [L, NP, PS, KVH*Dh]: the stacked FUSED pool
    v_pages: jax.Array,
    layer: jax.Array,      # scalar int32: the layer this call reads
    page_table: jax.Array, # [B, MP] int32
    past_len: jax.Array,   # [B] int32: tokens already in the pages
    valid_len: jax.Array,  # [B] int32: valid tokens of the chunk
    window: Optional[jax.Array] = None,  # scalar int32; 0/None => full
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns ``[B, T, NH, Dh]``: causal attention of a chunk's ``T``
    queries a row over the row's paged past and the chunk's own keys
    (module docstring). Queries sit at ``past_len + t``."""
    lowering.record_kernel(
        lowering.PAGED_CHUNK, interpret=interpret, heads=q.shape[2]
    )
    B, T, NH, Dh = q.shape
    L, NP, PS, KD = k_pages.shape
    KVH = KD // Dh
    G = NH // KVH
    MP = page_table.shape[1]
    tiles = chunk_tiles(
        T, NH, KVH, Dh, PS,
        pool_bytes=k_pages.dtype.itemsize, io_bytes=q.dtype.itemsize,
    )
    assert tiles is not None, (q.shape, k_pages.shape)
    TQ, Tp, BK, NPG = tiles

    def flat(x):
        # [B, T, heads, Dh] -> [B, Tp, heads*Dh]: a bitcast, and zeros
        # behind the chunk where a tile is longer than it
        x = x.reshape(B, T, -1)
        return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0))) if Tp > T else x

    kernel = functools.partial(
        _paged_chunk_kernel, kvh=KVH, groups=G, max_pages_per_seq=MP,
        page_size=PS, own_block=BK, scale=Dh ** -0.5,
    )
    scalars = [
        page_table.reshape(-1).astype(jnp.int32),
        past_len.astype(jnp.int32),
        valid_len.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(1),
    ]
    RQ = TQ * G
    tile = pl.BlockSpec((1, TQ, NH * Dh), lambda b, qb, *s: (b, qb, 0))
    own = pl.BlockSpec((1, Tp, KD), lambda b, qb, *s: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)   # the pools stay in HBM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, Tp // TQ),
        in_specs=[tile, pool, pool, own, own],
        out_specs=tile,
        scratch_shapes=[
            pltpu.VMEM((2, NPG * PS, KD), k_pages.dtype),
            pltpu.VMEM((2, NPG * PS, KD), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, NPG)),
            pltpu.SemaphoreType.DMA((2, NPG)),
            pltpu.VMEM((KVH, RQ, Dh), q.dtype),
            pltpu.VMEM((KVH, RQ, 128), jnp.float32),
            pltpu.VMEM((KVH, RQ, 128), jnp.float32),
            pltpu.VMEM((KVH, RQ, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tp, NH * Dh), q.dtype),
        # the fetches pass from grid step to grid step: the grid runs in
        # order (nothing lost on one-core v5e)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*scalars, flat(q), k_pages, v_pages, flat(k), flat(v))
    return out[:, :T].reshape(B, T, NH, Dh)
