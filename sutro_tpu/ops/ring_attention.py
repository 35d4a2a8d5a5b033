"""Ring-attention sequence/context parallelism over the ``seq`` mesh axis.

The reference has no long-context mechanism beyond server-side truncation
(`/root/reference/sutro/sdk.py:457,480` — ``truncate_rows``); this is the
TPU-native capability that makes truncation optional (SURVEY §5.7): shard
the sequence over devices so a prompt longer than one chip's HBM still
prefills at full attention.

Design (blockwise/flash over a device ring — the standard TPU recipe):

- Queries stay resident: each ``seq``-axis device holds one contiguous
  chunk of the sequence's Q, K and V (``[B, T/S, ...]``).
- K/V chunks rotate around the ring with ``lax.ppermute`` (neighbor
  exchange over ICI); after S steps every device has seen every K/V block.
- Each step folds its block into a running flash-attention accumulator
  (fp32 running max ``m``, denominator ``l``, numerator ``acc``) so the
  softmax is exact — identical numerics to full attention up to fp32
  reduction order.
- Causality, padding validity, sliding windows, and gpt-oss attention
  sinks are all handled by *global position* masks, so correctness is
  independent of ring rotation order; with a sliding window the distant
  blocks simply contribute nothing.
- Composes with TP: the head axes of Q/K/V keep their ``model`` sharding
  inside the shard_map (heads are embarrassingly parallel in attention),
  so ring steps move only ``1/tp`` of the K/V per device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .attention import NEG_INF


def _ring_body(
    qg: jax.Array,       # [B, Tq, KVH, G, Dh] fp32
    q_pos: jax.Array,    # [B, Tq]
    scale: float,
    window: jax.Array,   # scalar int32; 0 => full attention
    carry,
):
    k_c, v_c, kp_c, kval_c, m, l, acc = carry
    scores = (
        jnp.einsum("btkgd,bskd->bkgts", qg, k_c.astype(jnp.float32)) * scale
    )  # [B, KVH, G, Tq, S]
    qp = q_pos[:, :, None]                  # [B, Tq, 1]
    kp = kp_c[:, None, :]                   # [B, 1, S]
    allowed = (kp <= qp) & kval_c[:, None, :]
    in_window = (qp - kp) < jnp.where(
        window > 0, window, jnp.iinfo(jnp.int32).max
    )
    allowed = allowed & in_window
    mask = allowed[:, None, None, :, :]     # [B, 1, 1, Tq, S]
    scores = jnp.where(mask, scores, NEG_INF)
    s_max = jnp.max(scores, axis=-1)        # [B, KVH, G, Tq]
    m_new = jnp.maximum(m, s_max)
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(mask, p, 0.0)             # exact zeros on masked entries
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bkgts,bskd->bkgtd", p, v_c.astype(jnp.float32)
    )
    return k_c, v_c, kp_c, kval_c, m_new, l, acc


def ring_attention_local(
    q: jax.Array,        # [B, Tq, NH_local, Dh] — this device's Q chunk
    k: jax.Array,        # [B, Tc, KVH_local, Dh] — this device's K chunk
    v: jax.Array,
    q_pos: jax.Array,    # [B, Tq] int32 global positions
    kv_pos: jax.Array,   # [B, Tc] int32 global positions of the K/V chunk
    kv_valid: jax.Array,  # [B, Tc] bool — real (non-pad) K/V tokens
    window: jax.Array,   # scalar int32 (0 = full)
    sink: jax.Array,     # [NH_local] fp32 (zeros when has_sink=False)
    *,
    axis_name: str,
    ring_size: int,
    has_sink: bool,
) -> jax.Array:
    """Per-shard body (call inside shard_map). Returns [B, Tq, NH, Dh]."""
    B, Tq, NH, Dh = q.shape
    KVH = k.shape[2]
    G = NH // KVH
    scale = Dh ** -0.5
    qg = q.reshape(B, Tq, KVH, G, Dh).astype(jnp.float32)

    m0 = jnp.full((B, KVH, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Tq), jnp.float32)
    acc0 = jnp.zeros((B, KVH, G, Tq, Dh), jnp.float32)
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]

    def body(i, carry):
        carry = _ring_body(qg, q_pos, scale, window, carry)
        k_c, v_c, kp_c, kval_c, m, l, acc = carry
        if ring_size > 1 and i < ring_size - 1:  # last rotation is unused
            k_c = jax.lax.ppermute(k_c, axis_name, perm)
            v_c = jax.lax.ppermute(v_c, axis_name, perm)
            kp_c = jax.lax.ppermute(kp_c, axis_name, perm)
            kval_c = jax.lax.ppermute(kval_c, axis_name, perm)
        return k_c, v_c, kp_c, kval_c, m, l, acc

    carry = (k, v, kv_pos, kv_valid, m0, l0, acc0)
    for i in range(ring_size):  # static unroll; perm list is static anyway
        carry = body(i, carry)
    *_, m, l, acc = carry

    if has_sink:
        sk = sink.astype(jnp.float32).reshape(KVH, G)
        l = l + jnp.exp(sk[None, :, :, None] - m)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # [B, KVH, G, Tq, Dh] -> [B, Tq, NH, Dh]
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, NH, Dh).astype(q.dtype)
    )


def ring_self_attention(
    mesh: Mesh,
    q: jax.Array,              # [B, T, NH, Dh]
    k: jax.Array,              # [B, T, KVH, Dh]
    v: jax.Array,
    *,
    positions: jax.Array,      # [B, T] int32
    valid_len: jax.Array,      # [B] int32
    window: Optional[jax.Array] = None,
    sink: Optional[jax.Array] = None,
    axis_name: str = "seq",
    head_axis: Optional[str] = "model",
) -> jax.Array:
    """Sequence-parallel causal self-attention (prefill; no past).

    ``T`` must be a multiple of ``mesh.shape[axis_name]`` (the runner pads
    prefill buckets accordingly). Head axes stay sharded over
    ``head_axis`` so the op composes with TP.
    """
    S = mesh.shape[axis_name]
    B, T, NH, _ = q.shape
    if T % S:
        raise ValueError(f"T={T} not divisible by seq axis size {S}")
    kv_valid = jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
    win = (
        jnp.asarray(0, jnp.int32)
        if window is None
        else jnp.asarray(window, jnp.int32)
    )
    has_sink = sink is not None
    sk = (
        jnp.zeros((NH,), jnp.float32)
        if sink is None
        else sink.astype(jnp.float32)
    )

    h = head_axis if (head_axis and mesh.shape.get(head_axis, 1) > 1) else None
    spec_qkv = P(None, axis_name, h, None)
    spec_bt = P(None, axis_name)

    fn = jax.shard_map(
        functools.partial(
            ring_attention_local,
            axis_name=axis_name,
            ring_size=S,
            has_sink=has_sink,
        ),
        mesh=mesh,
        in_specs=(
            spec_qkv, spec_qkv, spec_qkv, spec_bt, spec_bt, spec_bt,
            P(), P(h),
        ),
        out_specs=spec_qkv,
        check_vma=False,
    )
    return fn(q, k, v, positions, positions, kv_valid, win, sk)
