"""Attention ops.

``chunk_attention`` is the single attention entry point for both prefill
(T=chunk, no past) and decode (T=1, past gathered from the paged KV cache).
The reference has no kernels at all (SURVEY §2.3); this is the TPU-native
hot path. Two implementations sit behind one signature:

- a pure-``jnp`` path (XLA fuses it well; used on CPU tests and as the
  reference the kernels are tested against), and
- Pallas flash/paged kernels (ops/pallas_flash.py, ops/pallas_paged.py),
  dispatched with ``use_pallas=True`` on TPU. Their shape gates send
  unsupported calls to the jnp path; every such call is counted
  (ops/lowering.py) so a chip run can tell which path it built.

Semantics handled here, uniformly: GQA head grouping, causal masking within
the chunk, past-length masking, per-layer sliding windows (Gemma3 5:1
local:global, gpt-oss alternating — SURVEY §5.7), and gpt-oss learnable
attention sinks (an extra per-head softmax logit that absorbs probability
mass).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import lowering

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
    # in place; the fallback gathers [layer, page_table] once.
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
) -> jax.Array:
    """Returns [B, T, NH, Dh]."""
    B, T = q.shape[:2]
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
    if past_k_pages is not None:
        if use_pallas and T == 1:
            from .pallas_paged import paged_decode_attention, paged_decode_supported

            if paged_decode_supported(q[:, 0], past_k_pages, page_table):
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
                split = bool(pfx_groups) and kernel_mesh is None
                if split:
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
                ops.update(
                    {k_: v_ for k_, v_ in optional.items() if v_ is not None}
                )
                out = lowering.shard_over_model(
                    kernel_mesh,
                    paged_decode_attention,
                    ops, _PAGED_SPECS, P(None, "model", None),
                )
                return out[:, None]
        from ..engine.kvcache import gather_kv_layer

        if use_pallas:
            # T>1 over a paged past (chunked prefill, verify forwards)
            # gathers by design; T==1 lands here only when the shape
            # gate refused the kernel
            lowering.record_reference("paged_decode")
        past_k, past_v = gather_kv_layer(
            past_k_pages, past_v_pages, layer, page_table, k.shape[2],
            k_scale=past_k_scale, v_scale=past_v_scale,
            out_dtype=q.dtype,
        )

    if use_pallas:
        from .pallas_flash import flash_prefill, flash_prefill_supported

        if past_k is None and flash_prefill_supported(q, k, window, sink):
            ops = dict(q=q, k=k, v=v)
            if window is not None:
                ops["window"] = jnp.asarray(window, jnp.int32)
            if sink is not None:
                ops["sink"] = sink
            return lowering.shard_over_model(
                kernel_mesh, flash_prefill, ops, _FLASH_SPECS,
                P(None, None, "model", None),
            )
        if T > 1:
            lowering.record_reference("flash_prefill")

    B, T, NH, Dh = q.shape
    KVH = k.shape[2]
    G = NH // KVH
    scale = Dh ** -0.5

    if past_k is not None:
        ctx = past_k.shape[1]
        key_segs = [past_k, k]
        val_segs = [past_v, v]
        pos_segs = [
            jnp.broadcast_to(
                jnp.arange(ctx, dtype=jnp.int32)[None], (B, ctx)
            ),
            positions,
        ]
        valid_segs = [
            jnp.arange(ctx, dtype=jnp.int32)[None] < past_len[:, None],
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

    # Mask: causal (key_pos <= q_pos), key validity, sliding window.
    qp = positions[:, :, None]                     # [B, T, 1]
    kp = key_pos[:, None, :]                       # [B, 1, S]
    allowed = (kp <= qp) & key_valid[:, None, :]
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
