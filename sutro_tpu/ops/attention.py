"""Attention ops.

``chunk_attention`` is the single attention entry point for both prefill
(T=chunk, no past) and decode (T=1, past gathered from the paged KV cache).
The reference has no kernels at all (SURVEY §2.3); this is the TPU-native
hot path. Three implementations sit behind one signature:

- a pure-``jnp`` path (XLA fuses it well; used on CPU tests and as the
  reference the kernels are tested against),
- ``paged_decode_xla``: one decode step (T=1) over a paged past in plain
  XLA, on the gathered pages in the layout the pool gave them, for every
  call the paged kernel does not take, and
- Pallas flash/paged kernels (ops/pallas_flash.py, ops/pallas_paged.py;
  ops/pallas_chunk.py for a chunk of several tokens over a paged past),
  dispatched with ``use_pallas=True`` on TPU. Their shape gates send
  unsupported calls to the XLA paths; every such call is counted
  (ops/lowering.py) so a chip run can tell which path it built.

Semantics handled here, uniformly: GQA head grouping, causal masking within
the chunk, past-length masking, per-layer sliding windows (Gemma3 5:1
local:global, gpt-oss alternating — SURVEY §5.7), and gpt-oss learnable
attention sinks (an extra per-head softmax logit that absorbs probability
mass).

``latent_attention`` is the attention of a LATENT layer (models/
transformer.py ``mla_mixer``), whose cache keeps one row a token that
every head reads for both products: the expanded form over a chunk with
no past (per-head K and V of two widths), and the absorbed form over
gathered latent pages, a fused window's rows and the chunk's own rows
under one softmax, in plain XLA a block of queries at a time. Under
``use_pallas`` one decode step takes the paged kernel's latent variant
(one fetch of a row's pages for both products) and a chunk with no past
the flash kernel at zero-padded heads (``_latent_kernels``); a chunk of
several tokens over a paged past still gathers (``chunk_attention``'s
reads the pages in place since ops/pallas_chunk.py).
A latent layer with an INDEXER (learned sparse attention) runs its
softmax over the positions the indexer selects: ops/sparse_attention.py,
which falls to ``latent_attention`` while the selection is everything.

``live_window`` (static) marks a layer whose pool is the WINDOW pool of a
model that keeps K/V a pool a kind (engine/kvcache.py): the pool holds a
row's last ``live_window`` positions and the pages before them may belong
to another sequence by now, so every path reads from the page of position
``max(past_len - live_window + 1, 0)`` on and nothing before it
(``live_pages``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import lowering
from .pages import (
    first_live_page, gather_kv_layer, gather_pages, window_span_pages,
)

NEG_INF = -1e30


# how each kernel operand shards over the mesh's "model" axis: heads
# and the fused KV-head-major KD axis split in whole-KV-head blocks
# (parallel/sharding.py), everything else replicates. The pools are the
# stacked [L, NP, PS, KD] arrays, so each shard's kernel sees its own
# [L, NP, PS, KD/tp] stack and indexes the layer itself
_PAGED_SPECS = dict(
    q=P(None, "model", None),
    k_pages=P(None, None, None, "model"),
    v_pages=P(None, None, None, "model"),
    layer=P(), page_table=P(), past_len=P(),
    k_cur=P(None, "model", None), v_cur=P(None, "model", None),
    window=P(), sink=P("model"),
    win_k=P(None, None, "model"), win_v=P(None, None, "model"),
    win_len=P(), k_scale=P(), v_scale=P(),
)
_FLASH_SPECS = dict(
    q=P(None, None, "model", None), k=P(None, None, "model", None),
    v=P(None, None, "model", None), window=P(), sink=P("model"),
    valid_len=P(),
)


def _prefix_carry(
    q, k_pages, v_pages, layer, k_scale, v_scale, pfx_groups, q_pos, win
) -> dict:
    """The paged kernel's initial online-softmax carry over the
    job-shared prefix groups (Hydragen-style split decode): each
    group's prefix attention is computed ONCE for the whole batch and
    the per-row carries combine exactly, because groups have DISJOINT
    member rows — cold rows contribute (-inf, 0, 0) to max/sum/sum."""
    from .pallas_paged import (
        prefix_attention_carry,
        prefix_attention_carry_pallas,
        prefix_carry_supported,
    )

    PS = k_pages.shape[2]
    # in-place carry kernel when shapes allow: the shared pages are
    # read straight from the stacked HBM pool ((layer, page)-indexed
    # BlockSpecs); otherwise one XLA gather on [layer, pages] computes
    # the identical carry
    in_place = prefix_carry_supported(q, k_pages, k_scale)
    m0 = l0 = acc0 = None
    pfx_cnt = jnp.zeros_like(q_pos)
    for pages_g, len_g in pfx_groups:
        if in_place:
            mg, lg, ag = prefix_attention_carry_pallas(
                q, k_pages, v_pages, layer, pages_g, len_g, q_pos, win,
            )
        else:
            mg, lg, ag = prefix_attention_carry(
                q, k_pages, v_pages, layer, pages_g, len_g, q_pos, win,
                k_scale=k_scale, v_scale=v_scale,
            )
        if m0 is None:
            m0, l0, acc0 = mg, lg, ag
        else:
            m0 = jnp.maximum(m0, mg)
            l0 = l0 + lg
            acc0 = acc0 + ag
        pfx_cnt = pfx_cnt + len_g // PS
    return dict(pfx_cnt=pfx_cnt, m0=m0, l0=l0, acc0=acc0)


def live_pages(page_table, past_len, live_window: int, page_size: int):
    """``(table [B, MPw], first [B])``: the slots of each row's table
    that can hold a position a query at ``past_len`` or later sees
    through a window of ``live_window``, from slot ``first`` on. MPw is
    static: the pages a window spans however it lies on them. A slot
    past the table's end repeats its last one; its positions (counted
    from the unclipped slot) are past every ``past_len``, so it is
    masked like any page not yet written."""
    MP = page_table.shape[1]
    span = min(MP, window_span_pages(live_window, 0, page_size))
    first = first_live_page(past_len, live_window, page_size)
    slots = first[:, None] + jnp.arange(span, dtype=jnp.int32)[None]
    return (
        jnp.take_along_axis(page_table, jnp.minimum(slots, MP - 1), axis=1),
        first,
    )


@jax.named_scope("paged_decode_xla")
def paged_decode_xla(
    q: jax.Array,          # [B, NH, Dh] — current-step queries
    k_pages: jax.Array,    # [L, NP, PS, KVH*Dh] — the stacked FUSED pool
    v_pages: jax.Array,
    layer: jax.Array,      # scalar int32 — the layer this call reads
    page_table: jax.Array, # [B, MP] int32
    past_len: jax.Array,   # [B] int32 — tokens already in the cache
    k_cur: jax.Array,      # [B, KVH, Dh] — current token K (post-RoPE)
    v_cur: jax.Array,
    window: Optional[jax.Array] = None,  # scalar int32; 0 => full attention
    sink: Optional[jax.Array] = None,    # [NH] logits or None
    win_k: Optional[jax.Array] = None,   # [B, W, KVH*Dh] fused-window K
    win_v: Optional[jax.Array] = None,
    win_len: Optional[jax.Array] = None,  # scalar int32 — valid slots
    k_scale: Optional[jax.Array] = None,  # [L, NP, PS] int8-KV scales
    v_scale: Optional[jax.Array] = None,
    live_window: int = 0,  # static: gather the window's pages only
) -> jax.Array:
    """One decode step's attention in plain XLA, ``[B, NH, Dh]``: what
    ``paged_decode_attention`` computes, for the calls the kernel does
    not take (heads that are no multiple of 128 lanes, ``use_pallas``
    off, a mesh that shards more than ``model``, the CPU).

    The gathered pages are used WHERE THEY LIE, ``[B, MP*PS, KD]`` with
    the fused ``KVH*Dh`` axis on the lanes. A head-split view ``[B, S,
    KVH, Dh]`` of them is a relayout (a minor axis of 64 is half a lane
    tile), a concatenation with the window's buffer and the current
    token copies the context for nine more positions, and a float32
    cast is a third copy: ~20 ms of a 55 ms step at granite's shapes
    (PERF.md §6, PR 33). Instead the queries are spread block-diagonally
    over the fused axis (row n carries q[n] in lane block n // G, zeros
    elsewhere: the form the Pallas kernel uses inside a row), so scores
    are ``[NH, KD] x [KD, S]`` and values ``[NH, S] x [S, KD]`` a row,
    KVH times the needed FLOPs and no relayout; the diagonal blocks of
    the small ``[B, NH, KD]`` result are picked out at the end. Past
    pages, the window's buffer and the current token are three segments
    with their own scores and masks under ONE softmax (shared maximum
    and sum), never one concatenated context. Operands reach the MXU in
    the dtype they have, accumulation is float32, the probabilities are
    rounded to the values' dtype as the MXU's default precision rounds
    them anyway; masks, softmax and sink are float32."""
    lowering.record_xla_decode()
    B, NH, Dh = q.shape
    PS, KD = k_pages.shape[2:]
    KVH = KD // Dh
    G = NH // KVH
    first = None
    if live_window:
        page_table, first = live_pages(page_table, past_len, live_window, PS)
    S = page_table.shape[1] * PS
    scale = Dh ** -0.5
    f32 = jnp.float32

    # ONE gather a pool (int8 K/V dequantized into the compute dtype);
    # [B*MP, PS, KD] -> [B, S, KD] merges major axes only
    kp, vp = gather_pages(
        k_pages, v_pages, layer, page_table, k_scale, v_scale, q.dtype
    )
    kp = kp.reshape(B, S, KD)
    vp = vp.reshape(B, S, KD)

    # block-diagonal queries [B, NH, KD]: row n lives in lane block n // G
    block_of_row = jnp.arange(NH, dtype=jnp.int32)[:, None] // G  # [NH, 1]
    q_bd = jnp.where(
        block_of_row == jnp.arange(KD, dtype=jnp.int32)[None, :] // Dh,
        jnp.tile(q, (1, 1, KVH)), jnp.zeros((), q.dtype),
    )

    def scores(keys, allowed):   # [B, X, KD], [B|1, X] -> [B, NH, X] f32
        s = jnp.einsum(
            "bnc,bxc->bnx", q_bd, keys, preferred_element_type=f32
        ) * scale
        return jnp.where(allowed[:, None, :], s, NEG_INF)

    def values(p, vals):         # [B, NH, X] f32, [B, X, KD] -> [B, NH, KD]
        return jnp.einsum(
            "bnx,bxc->bnc", p.astype(vals.dtype), vals,
            preferred_element_type=f32,
        )

    wl = jnp.asarray(0 if win_len is None else win_len, jnp.int32)
    win = jnp.asarray(0 if window is None else window, jnp.int32)
    span = jnp.where(win > 0, win, jnp.iinfo(jnp.int32).max)
    q_pos = past_len + wl                                   # [B]
    t = jnp.arange(S, dtype=jnp.int32)[None]
    if first is not None:
        t = t + first[:, None] * PS
    segs = [(
        scores(kp, (t < past_len[:, None]) & (q_pos[:, None] - t < span)),
        vp,
    )]
    if win_k is not None and win_k.shape[1] > 0:
        # window tokens sit at past_len + slot, valid while slot < win_len
        slot = jnp.arange(win_k.shape[1], dtype=jnp.int32)[None]
        segs.append(
            (scores(win_k, (slot < wl) & (wl - slot < span)), win_v)
        )
    # the current token: always attended, a product of [B, NH, Dh] alone
    s_cur = jnp.einsum(
        "bkgd,bkd->bkg", q.reshape(B, KVH, G, Dh), k_cur,
        preferred_element_type=f32,
    ).reshape(B, NH) * scale

    m = s_cur
    for s, _ in segs:
        m = jnp.maximum(m, jnp.max(s, axis=-1))
    if sink is not None:
        sink = sink.astype(f32)[None]
        m = jnp.maximum(m, sink)
    p_cur = jnp.exp(s_cur - m)
    denom = p_cur if sink is None else p_cur + jnp.exp(sink - m)
    acc = None                                              # [B, NH, KD] f32
    for s, vals in segs:
        p = jnp.exp(s - m[..., None])
        denom = denom + jnp.sum(p, axis=-1)
        acc = values(p, vals) if acc is None else acc + values(p, vals)
    # row n's output is the lane block n // G of its accumulator
    own = block_of_row == jnp.arange(KVH, dtype=jnp.int32)[None, :]
    out = jnp.sum(
        jnp.where(own[None, :, :, None], acc.reshape(B, NH, KVH, Dh), 0.0),
        axis=2,
    )
    out = out + p_cur[..., None] * jnp.repeat(v_cur.astype(f32), G, axis=1)
    return (out / denom[..., None]).astype(q.dtype)


def chunk_attention(
    q: jax.Array,                       # [B, T, NH, Dh]
    k: jax.Array,                       # [B, T, KVH, Dh] (chunk, post-RoPE)
    v: jax.Array,                       # [B, T, KVH, Dh]
    *,
    positions: jax.Array,               # [B, T] global positions of queries
    valid_len: jax.Array,               # [B] valid tokens in the chunk
    past_k: Optional[jax.Array] = None, # [B, CTX, KVH, Dh]
    past_v: Optional[jax.Array] = None,
    past_len: Optional[jax.Array] = None,  # [B]
    # paged past: the WHOLE stacked page pool + the layer to read + the
    # table; mutually exclusive with past_k/past_v. Pools carry the
    # FUSED [L, NP, PS, KVH*Dh] layout (engine/kvcache.py) and are never
    # sliced per layer: the Pallas paged kernel DMAs pool[layer, page]
    # in place; the XLA paths gather [layer, page_table] once.
    past_k_pages: Optional[jax.Array] = None,  # [L, NP, PS, KVH*Dh]
    past_v_pages: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,         # scalar int32
    # int8 KV mode: per-token dequant scales, stacked like the pages
    past_k_scale: Optional[jax.Array] = None,  # [L, NP, PS] f32
    past_v_scale: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,    # [B, MP] int32
    window: Optional[jax.Array] = None,    # scalar int32; 0 => full attention
    sink: Optional[jax.Array] = None,      # [NH] attention-sink logits
    use_pallas: bool = False,
    ring_mesh=None,                        # Mesh with a >1 "seq" axis =>
                                           # sequence-parallel ring prefill
    # fused-decode window buffer (runner.decode_multi): K/V of tokens
    # sampled earlier in the window, not yet written to the page pool.
    # win_k/win_v [B, W, KVH*Dh] (FUSED trailing axis, matching the page
    # pool); win_len scalar = valid slots, positions are past_len + slot.
    win_k: Optional[jax.Array] = None,
    win_v: Optional[jax.Array] = None,
    win_len: Optional[jax.Array] = None,
    # shared-prefix (Hydragen-style) decode: each group is a
    # ``(pages [Pp_g] int32, pfx_len [B] int32)`` pair — member rows'
    # tables START with the group's shared pages (pfx_len 0 = row not
    # in that group; groups are disjoint). The Pallas path computes
    # each group's prefix attention once for the whole batch (one HBM
    # read of the shared pages per layer-step instead of one per row),
    # combines the per-row carries exactly (max/sum/sum over disjoint
    # groups), and injects them as the paged kernel's initial
    # online-softmax carry. The fallback path ignores this (the tables
    # still contain the prefix pages, so its full-table gather computes
    # the identical function).
    pfx_groups: Optional[tuple] = None,
    # mesh whose "model" axis shards the heads: the Pallas calls run as
    # a shard_map over it (ops/lowering.shard_over_model). The
    # shared-prefix carry is not partitioned; under a mesh its groups
    # are ignored like on the fallback path (same function).
    kernel_mesh=None,
    # static: the paged past is a WINDOW pool's (module docstring)
    live_window: int = 0,
    # static: the mask is causal BY BLOCKS of this many positions
    # (``ModelConfig.block_length``): a query sees every key of an
    # earlier block and the WHOLE of its own. 1 is the causal mask, and
    # the branch is Python's: every program of a causal model is the
    # one it was
    block_length: int = 1,
) -> jax.Array:
    """Returns [B, T, NH, Dh]."""
    B, T = q.shape[:2]
    if block_length > 1 and (
        ring_mesh is not None or live_window or pfx_groups
    ):
        raise NotImplementedError(
            "a mask by blocks (block_length) under ring attention, a "
            "window pool or a shared prefix's carry"
        )
    if (
        ring_mesh is not None
        and past_k is None
        and past_k_pages is None
        and T > 1
    ):
        from .ring_attention import ring_self_attention

        return ring_self_attention(
            ring_mesh, q, k, v,
            positions=positions, valid_len=valid_len,
            window=window, sink=sink,
        )
    if past_k_pages is not None and T == 1:
        # one decode step: the Pallas kernel where its gate takes the
        # shape, else the same function in plain XLA; either way one
        # call a shard of the mesh's "model" axis (XLA cannot partition
        # a Mosaic call, and would answer the XLA form's block-diagonal
        # products with an all-gather of the context)
        win = (
            jnp.asarray(0, jnp.int32) if window is None
            else jnp.asarray(window, jnp.int32)
        )
        ops = dict(
            q=q[:, 0], k_pages=past_k_pages, v_pages=past_v_pages,
            layer=layer,
            page_table=page_table, past_len=past_len,
            k_cur=k[:, 0], v_cur=v[:, 0], window=win,
        )
        optional = dict(
            sink=sink, win_k=win_k, win_v=win_v, win_len=win_len,
            k_scale=past_k_scale, v_scale=past_v_scale,
        )
        decode = functools.partial(paged_decode_xla, live_window=live_window)
        if use_pallas:
            from .pallas_paged import paged_decode_attention, paged_decode_supported

            if paged_decode_supported(q[:, 0], past_k_pages):
                decode = paged_decode_attention
                if live_window:
                    decode = functools.partial(decode, window_start=True)
                if pfx_groups and kernel_mesh is None:
                    optional.update(
                        _prefix_carry(
                            q[:, 0], past_k_pages, past_v_pages, layer,
                            past_k_scale, past_v_scale, pfx_groups,
                            past_len + (
                                win_len if win_len is not None else 0
                            ),
                            win,
                        )
                    )
            else:
                lowering.record_reference(
                    "paged_decode", q.shape[2],
                    "paged_decode_supported: heads of "
                    f"{q.shape[-1]} or pages of {past_k_pages.shape[2]}",
                )
        ops.update({k_: v_ for k_, v_ in optional.items() if v_ is not None})
        out = lowering.shard_over_model(
            kernel_mesh, decode, ops, _PAGED_SPECS, P(None, "model", None),
        )
        return out[:, None]
    if (
        past_k_pages is not None and block_length > 1 and T == block_length
        and use_pallas and kernel_mesh is None
        and sink is None and past_k_scale is None
    ):
        # ONE BLOCK of a model that generates by blocks, over its paged
        # past: every query sees the row's pages, the window's earlier
        # blocks and all T current keys, which is the paged kernel's own
        # shape with the block's queries beside a KV head's group
        # (ops/pallas_paged.paged_block_attention). No gather
        from .pallas_paged import paged_block_attention, paged_decode_supported

        if paged_decode_supported(q[:, 0], past_k_pages):
            return paged_block_attention(
                q, past_k_pages, past_v_pages, layer, page_table, past_len,
                k, v, win_k=win_k, win_v=win_v, win_len=win_len,
            )
    if past_k_pages is not None and T > 1 and block_length == 1 and use_pallas:
        # a chunk of several tokens over a paged past (a verify forward,
        # a job's suffix over its shared prefix, a chunk of a chunked
        # prefill) reads the row's pages where they lie, for the pages
        # the row holds (ops/pallas_chunk.py). No gather
        from .pallas_chunk import paged_chunk_attention, paged_chunk_supported

        if paged_chunk_supported(
            q, past_k_pages, k_scale=past_k_scale, sink=sink, win_k=win_k,
            live_window=live_window, kernel_mesh=kernel_mesh,
        ):
            return paged_chunk_attention(
                q, k, v, past_k_pages, past_v_pages, layer, page_table,
                past_len, valid_len, window,
            )
    if past_k_pages is not None:
        if use_pallas:
            # what neither kernel above takes GATHERS the row's whole
            # table: a chunk whose heads, pages or operands
            # ``paged_chunk_supported`` refuses (heads of 64, int8 K/V,
            # a sink, a window pool, a mesh) and a block whose heads the
            # block form does not take
            lowering.record_reference("paged_decode")
            lowering.record_reference(
                lowering.PAGED_CHUNK, q.shape[2],
                "paged_chunk_supported"
                + (": a window pool's pages" if live_window else ""),
            )
        past_first = None
        if live_window:
            page_table, past_first = live_pages(
                page_table, past_len, live_window, past_k_pages.shape[2]
            )
        past_k, past_v = gather_kv_layer(
            past_k_pages, past_v_pages, layer, page_table, k.shape[2],
            k_scale=past_k_scale, v_scale=past_v_scale,
            out_dtype=q.dtype,
        )

    if use_pallas:
        from .pallas_flash import flash_prefill, flash_prefill_supported

        if past_k is None and flash_prefill_supported(q, k, window, sink):
            ops = dict(q=q, k=k, v=v, valid_len=valid_len)
            # a window the caller knows before tracing bounds the walk:
            # a layer KIND's (``live_window``), and a constant's (the
            # full layers' 0 beside them). A scan's layer brings a
            # tracer, which the kernel reads as it runs
            if not live_window and window is not None:
                if isinstance(window, jax.core.Tracer):
                    ops["window"] = jnp.asarray(window, jnp.int32)
                else:
                    live_window = int(window)
            if sink is not None:
                ops["sink"] = sink
            return lowering.shard_over_model(
                kernel_mesh,
                functools.partial(
                    flash_prefill, block_length=block_length,
                    live_window=live_window,
                ),
                ops, _FLASH_SPECS,
                P(None, None, "model", None),
            )
        if T > 1:
            lowering.record_reference(
                "flash_prefill", q.shape[2],
                "a chunk over a past" if past_k is not None
                else f"flash_prefill_supported: [{T}, {q.shape[2]}, "
                f"{q.shape[-1]}] over {k.shape[2]} KV heads",
            )

    B, T, NH, Dh = q.shape
    KVH = k.shape[2]
    G = NH // KVH
    scale = Dh ** -0.5

    if past_k is not None:
        ctx = past_k.shape[1]
        key_segs = [past_k, k]
        val_segs = [past_v, v]
        past_pos = jnp.broadcast_to(
            jnp.arange(ctx, dtype=jnp.int32)[None], (B, ctx)
        )
        if past_k_pages is not None and live_window:
            past_pos = past_pos + past_first[:, None] * past_k_pages.shape[2]
        pos_segs = [past_pos, positions]
        valid_segs = [
            past_pos < past_len[:, None],
            jnp.arange(T, dtype=jnp.int32)[None] < valid_len[:, None],
        ]
        if win_k is not None and win_k.shape[1] > 0:
            # fused-window tokens: positions past_len + slot, valid
            # while slot < win_len (they are not in the pages yet);
            # buffers arrive lane-fused [B, W, KVH*Dh]
            W = win_k.shape[1]
            slot = jnp.arange(W, dtype=jnp.int32)[None]
            key_segs.insert(1, win_k.reshape(B, W, KVH, Dh))
            val_segs.insert(1, win_v.reshape(B, W, KVH, Dh))
            pos_segs.insert(1, past_len[:, None] + slot)
            valid_segs.insert(
                1, jnp.broadcast_to(slot < win_len, (B, W))
            )
        keys = jnp.concatenate(key_segs, axis=1)
        vals = jnp.concatenate(val_segs, axis=1)
        key_pos = jnp.concatenate(pos_segs, axis=1)
        key_valid = jnp.concatenate(valid_segs, axis=1)
    else:
        keys, vals = k, v
        key_pos = positions
        key_valid = jnp.arange(T, dtype=jnp.int32)[None] < valid_len[:, None]

    S = keys.shape[1]
    qg = q.reshape(B, T, KVH, G, Dh).astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, kf) * scale  # [B,KVH,G,T,S]

    # Mask: causal (key_pos <= q_pos; by blocks, the key's block no
    # later than the query's), key validity, sliding window.
    qp = positions[:, :, None]                     # [B, T, 1]
    kp = key_pos[:, None, :]                       # [B, 1, S]
    if block_length > 1:
        allowed = (kp // block_length <= qp // block_length)
    else:
        allowed = kp <= qp
    allowed = allowed & key_valid[:, None, :]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        in_window = (qp - kp) < jnp.where(win > 0, win, jnp.iinfo(jnp.int32).max)
        allowed = allowed & in_window
    # mask shape [B,1,1,T,S] broadcasts over (KVH, G)
    scores = jnp.where(allowed[:, None, None, :, :], scores, NEG_INF)

    if sink is not None:
        sink_col = sink.astype(jnp.float32).reshape(1, KVH, G, 1, 1)
        sink_col = jnp.broadcast_to(sink_col, (B, KVH, G, T, 1))
        scores = jnp.concatenate([scores, sink_col], axis=-1)
        weights = jax.nn.softmax(scores, axis=-1)[..., :S]
    else:
        weights = jax.nn.softmax(scores, axis=-1)

    out = jnp.einsum("bkgts,bskd->btkgd", weights, vals.astype(jnp.float32))
    return out.reshape(B, T, NH, Dh).astype(q.dtype)


#: sides of the flash body's square blocks for a latent layer's expanded
#: heads: every query head has K and V of its own, so a grid step is ONE
#: head's two products, and at the kernel's own 128 the step's fixed
#: cost was most of it (PERF.md section 6, PR 42)
_LATENT_FLASH_BLOCKS = (1024, 512, 256)     # the largest that divides the chunk


def _latent_kernels(q, k, v, *, scale, pages, layer, page_table, past_len,
                    win_rows, win_len, value_width):
    """``latent_attention`` through the Pallas kernels where one takes
    the call, else None (the XLA form, counted ``reference``). ONE
    decode step over the latent pages: the paged kernel's latent
    variant (``v_pages`` None: a row's pages fetched once for both
    products, every head to the one stored row). A chunk with no past:
    the flash kernel's body with the heads' Q and K zero-padded to whole
    tiles of 128 lanes (192 -> 256: the padded products are wasted work
    and show in the prefill roofline) and V at its own width (128),
    under the layer's own scale, the operands in the dtype they have.
    A chunk of several tokens over a paged past (chunked prefill, verify
    forwards) still gathers the latent pages."""
    B, T, NH, Dq = q.shape
    if v is None and T == 1:
        from .pallas_paged import paged_decode_attention, paged_decode_supported

        if paged_decode_supported(q[:, 0], pages):
            win = {}
            if win_rows is not None and win_rows.shape[1] > 0:
                win = dict(win_k=win_rows, win_len=win_len)
            out = paged_decode_attention(
                q[:, 0], pages, None, layer, page_table, past_len,
                k[:, 0, None], None, jnp.asarray(0, jnp.int32),
                scale=scale, **win,
            )
            return out[:, None, :, :value_width]
        lowering.record_reference("paged_decode")
        return None
    if v is None:
        lowering.record_reference("paged_decode")
        return None
    block = latent_flash_block(q)
    if block is None:
        return None
    return latent_flash(q, k, v, scale=scale, block=block)


def _lane_padded(x):
    """The last axis zero-padded to whole tiles of 128 lanes."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, -x.shape[-1] % 128),))


def latent_flash_block(q) -> Optional[int]:
    """The side of the flash body's square blocks for a chunk of
    expanded heads ``q`` [B, T, NH, Dq]: the largest of
    ``_LATENT_FLASH_BLOCKS`` whose gate passes at the padded heads, or
    None where none does (counted ``reference``)."""
    from .pallas_flash import flash_prefill_supported

    qp = jax.ShapeDtypeStruct(
        q.shape[:-1] + (q.shape[-1] + -q.shape[-1] % 128,), q.dtype
    )
    block = next(
        (b for b in _LATENT_FLASH_BLOCKS
         if flash_prefill_supported(qp, qp, None, None, b)), None,
    )
    if block is None:
        lowering.record_reference("flash_prefill")
    return block


def latent_flash(q, k, v, *, scale, block, keep=None):
    """A chunk of expanded heads through the flash body at blocks of
    ``block`` (``latent_flash_block``), ``[B, T, NH, Dv]``; ``keep``
    ``[B, T, T]`` int8 is an indexer's selection
    (ops/sparse_attention.masked_attention)."""
    from .pallas_flash import flash_prefill

    return flash_prefill(
        _lane_padded(q), _lane_padded(k), _lane_padded(v), scale=scale,
        block=block, keep=keep,
    )[..., :v.shape[-1]]


def latent_attention(
    q: jax.Array,                 # [B, T, NH, Dq]
    k: jax.Array,                 # expanded [B, T, NH, Dq]; absorbed: the
    #                               chunk's own ROWS [B, T, Dq], one a token
    v: Optional[jax.Array],       # expanded [B, T, NH, Dv]; absorbed: None
    *,
    positions: jax.Array,         # [B, T] global positions of the queries
    valid_len: jax.Array,         # [B] valid tokens in the chunk
    scale: float,
    pages: Optional[jax.Array] = None,   # [L, NP, PS, Dq]: the latent pool
    layer: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,  # [B, MP]
    past_len: Optional[jax.Array] = None,    # [B]
    win_rows: Optional[jax.Array] = None,    # [B, W, Dq]: a fused window's
    win_len: Optional[jax.Array] = None,     # rows, at past_len + slot
    value_width: Optional[int] = None,   # absorbed: a row's leading values
    use_pallas: bool = False,
    block_q: int = 512,
) -> jax.Array:
    """Causal attention of a latent layer, ``[B, T, NH, Dv]`` (absorbed:
    ``[B, T, NH, value_width]``, still to go through the value half of
    the up-projection). ``v`` None is the ABSORBED form: keys are rows
    ``[.., Dq]`` shared by every head (the queries already carry the key
    half of the up-projection), and a row's first ``value_width``
    elements are its values, so ONE stored row serves both products:
    the value product runs over the whole row (an eighth more columns,
    dropped after) and no slice of the gathered pages is ever made. Its
    keys come in up to three segments under one softmax, never one
    concatenated context: the paged past (ONE gather of the rows'
    tables on the stacked pool, ``[B, MP * PS, Dq]``, used where it
    lies), a fused window's rows, and the chunk's own rows (causal).
    The EXPANDED form has the chunk alone, keys and values a head.

    Queries go a block of ``block_q`` at a time, so the float32 scores
    are ``[B, NH, block_q, keys]`` and never ``[B, NH, T, keys]`` (2 GB
    at 32 heads and a chunk of 4,096); a block of the chunk's own keys
    ends where the block's last query does, which skips the upper half
    of the causal square. Operands reach the MXU in the dtype they
    have; scores, masks and the softmax are float32, the probabilities
    are rounded to the values' dtype for their product."""
    B, T, NH = q.shape[:3]
    f32 = jnp.float32
    absorbed = v is None
    lowering.record_latent("absorbed" if absorbed else "expanded")
    if use_pallas:
        out = _latent_kernels(
            q, k, v, scale=scale, pages=pages, layer=layer,
            page_table=page_table, past_len=past_len, win_rows=win_rows,
            win_len=win_len, value_width=value_width,
        )
        if out is not None:
            return out
    # segments: (keys, values, key positions [B, X], key validity [B, X])
    segs = []
    if pages is not None:
        L, NP, PS, W = pages.shape
        MP = page_table.shape[1]
        at = layer * NP + page_table.reshape(-1)
        past = pages.reshape(L * NP, PS, W)[at].reshape(B, MP * PS, W)
        pos = jnp.broadcast_to(
            jnp.arange(MP * PS, dtype=jnp.int32)[None], (B, MP * PS)
        )
        segs.append((past, past, pos, pos < past_len[:, None]))
        if win_rows is not None and win_rows.shape[1] > 0:
            slot = jnp.arange(win_rows.shape[1], dtype=jnp.int32)[None]
            segs.append((
                win_rows, win_rows, past_len[:, None] + slot,
                jnp.broadcast_to(slot < win_len, (B, win_rows.shape[1])),
            ))
    own_valid = jnp.arange(T, dtype=jnp.int32)[None] < valid_len[:, None]
    outs = []
    for q0 in range(0, T, block_q):
        q1 = min(q0 + block_q, T)
        qb, qp = q[:, q0:q1], positions[:, q0:q1]
        # the chunk's own keys up to the block's last query
        own = (
            k[:, :q1], k[:, :q1] if absorbed else v[:, :q1],
            positions[:, :q1], own_valid[:, :q1],
        )
        scores = []
        for keys, _, kpos, kvalid in segs + [own]:
            if keys.ndim == 3:       # rows shared by the heads
                sc = jnp.einsum(
                    "btnc,bxc->bntx", qb, keys, preferred_element_type=f32
                )
            else:
                sc = jnp.einsum(
                    "btnd,bxnd->bntx", qb, keys, preferred_element_type=f32
                )
            ok = (kpos[:, None, :] <= qp[:, :, None]) & kvalid[:, None, :]
            scores.append(jnp.where(ok[:, None], sc * scale, NEG_INF))
        m = functools.reduce(
            jnp.maximum, [jnp.max(sc, axis=-1) for sc in scores]
        )                                                   # [B, NH, t]
        denom = acc = None
        for sc, (_, vals, _, _) in zip(scores, segs + [own]):
            p = jnp.exp(sc - m[..., None])
            d = jnp.sum(p, axis=-1)
            if vals.ndim == 3:
                o = jnp.einsum(
                    "bntx,bxc->btnc", p.astype(vals.dtype), vals,
                    preferred_element_type=f32,
                )
            else:
                o = jnp.einsum(
                    "bntx,bxnd->btnd", p.astype(vals.dtype), vals,
                    preferred_element_type=f32,
                )
            denom = d if denom is None else denom + d
            acc = o if acc is None else acc + o
        out = acc / jnp.moveaxis(denom, 1, 2)[..., None]    # [B, t, NH, D]
        if absorbed:
            out = out[..., :value_width]
        outs.append(out.astype(q.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
