"""Mellum 2 decoder (``model_type`` ``mellum``), plain ``jax.numpy`` in
float32.

Written from the published ``config.json`` keys of
Mellum2-12B-A2.5B-Instruct, independent of ``sutro_tpu/``: no kernels,
no cache, no pages, no batching, every expert in turn. ``h`` is the
residual stream:

    h = embed[ids]
    layer i:   a = RMSNorm(h)
               q, k, v = a Wq, a Wk, a Wv         (no biases)
               q, k = RMSNorm_per_head(q), RMSNorm_per_head(k)   (ASSUMED)
               q, k = Rotary_kind(q), Rotary_kind(k)   kind = layer_types[i]
               s = q k^T / sqrt(head_dim), causal; on a
                   "sliding_attention" layer only keys with
                   q_pos - k_pos < sliding_window
               h = h + softmax(s) v Wo
               x = RMSNorm(h)
               h = h + RoutedMLP(x)      (every mlp_layer_types entry "sparse")
    logits = RMSNorm(h) W_head            (untied)

    Rotary, rotate-half, pairs (j, j + head_dim/2), angle pos * f_j:
      "sliding_attention" (rope_type default): f_j = theta^(-2j/head_dim)
      "full_attention" (rope_type yarn, as rope_parameters states it):
          e_j = theta^(-2j/d)            extrapolation (the plain f_j)
          n_j = e_j / factor             interpolation
          c(r) = d ln(original / (2 pi r)) / (2 ln theta)
          low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), d - 1)
          ramp_j = clip((j - low) / (high - low), 0, 1)
          f_j = n_j ramp_j + e_j (1 - ramp_j)
          cos and sin times attention_factor, TAKEN FROM THE FILE

    RoutedMLP: Qwen3-MoE's (``qwen3_moe.routed_mlp``, the same
    mathematics): softmax over ALL experts, the num_experts_per_tok
    largest, divided by their sum (norm_topk_prob), no shared expert.

Attention is computed a block of ``QUERY_BLOCK`` queries at a time
against all keys, so that 4,096 positions fit (a block's scores are
heads x 512 x T float32); the numbers are those of the one product.

Weights arrive in the layout the system serves them in, stacked per
kind of layer: ``layers["attn"]`` (the full_attention layers:
``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``;
[L_full, ...]), ``layers["swa"]`` (the sliding_attention layers, the
same names, [L_window, ...]) and ``layers["moe"]`` (``mlp_norm``,
``router`` [L, H, E], ``we_gate``, ``we_up`` [L, E, H, F], ``we_down``
[L, E, F, H]); layer i's weights are its kind's next in order.

Assumed, and said so in the configuration file: the per-head RMSNorm of
q and k (the file's key set is Qwen3-MoE's, whose block has it and no
key for it); no multi-token-prediction module (no key for one).

What it refuses rather than guesses: a ``layer_types`` entry other than
``sliding_attention`` / ``full_attention``, an ``mlp_layer_types`` entry
other than ``sparse``, ``attention_bias`` true, tied embeddings, a
``rope_type`` other than ``default`` on the sliding layers or ``yarn``
on the full ones, a yarn section without its ``attention_factor``, and
whatever ``qwen3_moe.moe_dims_of`` refuses.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .qwen3_dense import F32, _embed, _head, _rms, layer_weight
from .qwen3_moe import TIE_MARGIN, moe_dims_of, routed_mlp

ROUTED = True
KINDS = {"sliding_attention": "swa", "full_attention": "attn"}
QUERY_BLOCK = 512

__all__ = [
    "ROUTED", "TIE_MARGIN", "dims_of", "logits_at", "logits_and_near_ties",
]


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    dims = moe_dims_of(cfg)
    types = tuple(cfg["layer_types"])
    unknown = sorted(set(types) - set(KINDS))
    if unknown:
        raise ValueError(f"mellum2_moe: layer_types entries {unknown} are not described here")
    if len(types) != dims["layers"]:
        raise ValueError(f"mellum2_moe: {len(types)} layer_types for {dims['layers']} layers")
    if set(cfg.get("mlp_layer_types") or ["sparse"]) != {"sparse"}:
        raise ValueError("mellum2_moe: every mlp_layer_types entry must be 'sparse'")
    if len(cfg.get("mlp_layer_types") or types) != len(types):
        raise ValueError("mellum2_moe: mlp_layer_types and layer_types differ in length")
    if cfg.get("attention_bias"):
        raise ValueError("mellum2_moe: attention_bias true is not described here")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("mellum2_moe: tied embeddings are not described here")
    rope = cfg["rope_parameters"]
    plain, yarn = rope["sliding_attention"], rope["full_attention"]
    if plain.get("rope_type") != "default" or yarn.get("rope_type") != "yarn":
        raise ValueError(
            "mellum2_moe: rope_parameters must be 'default' on sliding_attention "
            "and 'yarn' on full_attention"
        )
    if "attention_factor" not in yarn:
        raise ValueError("mellum2_moe: the yarn section must state attention_factor")
    dims.pop("theta", None)
    dims.update(
        types=types,
        window=int(cfg["sliding_window"]),
        theta_window=float(plain["rope_theta"]),
        theta_full=float(yarn["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original=float(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=float(yarn["attention_factor"]),
    )
    return dims


def inverse_frequencies(dims: Dict[str, Any], kind: str):
    """``(f [head_dim / 2] float32, what cos and sin are multiplied by)``
    of a layer of ``kind`` (module docstring)."""
    d = dims["head_dim"]
    j = np.arange(d // 2, dtype=np.float64)
    if kind == "swa":
        return (dims["theta_window"] ** (-2.0 * j / d)).astype(np.float32), 1.0
    theta = dims["theta_full"]
    extrapolation = theta ** (-2.0 * j / d)
    interpolation = extrapolation / dims["yarn_factor"]

    def correction(rotations: float) -> float:
        return d * math.log(
            dims["yarn_original"] / (2.0 * math.pi * rotations)
        ) / (2.0 * math.log(theta))

    low = max(math.floor(correction(dims["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(dims["yarn_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    f = interpolation * ramp + extrapolation * (1.0 - ramp)
    return f.astype(np.float32), dims["yarn_attention_factor"]


def rotary(x, positions, f, scale):
    """x [T, N, Dh]; rotate-half with angles ``positions * f``."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, None] * jnp.asarray(f)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(dims: Dict[str, Any], kind: str, w, h, positions):
    """``h + Attn(RMSNorm(h)) Wo`` over a whole sequence [T, H], for a
    layer of ``kind`` ("swa": the window and the plain rotary)."""
    NH, KVH, Dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    eps = dims["eps"]
    T = h.shape[0]
    f, scale = inverse_frequencies(dims, kind)
    x = _rms(h, w("attn_norm"), eps)
    q = (x @ w("wq")).reshape(T, NH, Dh)
    k = (x @ w("wk")).reshape(T, KVH, Dh)
    v = (x @ w("wv")).reshape(T, KVH, Dh)
    q = rotary(_rms(q, w("q_norm"), eps), positions, f, scale)
    k = rotary(_rms(k, w("k_norm"), eps), positions, f, scale)
    group = NH // KVH
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for lo in range(0, T, QUERY_BLOCK):
        qb, qp = q[lo : lo + QUERY_BLOCK], positions[lo : lo + QUERY_BLOCK]
        scores = jnp.einsum("tnd,snd->nts", qb, k) / jnp.sqrt(F32(Dh))
        seen = qp[:, None] >= positions[None, :]
        if kind == "swa":
            seen = seen & (qp[:, None] - positions[None, :] < dims["window"])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(
            jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
        )
    attn = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    return h + attn.reshape(T, NH * Dh) @ w("wo")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_jit(frozen_dims, kind, layers, m_index, f_index, h, positions):
    """One block over a whole sequence: (h [T, H], gap [T])."""
    dims = dict(frozen_dims)
    h = attention(dims, kind, layer_weight(layers[kind], m_index), h, positions)
    x = _rms(h, layer_weight(layers["moe"], f_index)("mlp_norm"), dims["eps"])
    y, gap = routed_mlp(dims, layers["moe"], f_index, x)
    return h + y, gap


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int],
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of layers whose routing there was a near tie."""
    dims = dims_of(cfg)
    frozen = tuple(sorted(dims.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    seen = {"attn": 0, "swa": 0}
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for i, name in enumerate(dims["types"]):
            kind = KINDS[name]
            h, gap = _layer_jit(
                frozen, kind, params["layers"], seen[kind], i, h, positions
            )
            seen[kind] += 1
            ties = ties + (gap[at] < TIE_MARGIN)
        if "lm_head" not in params:
            raise ValueError("mellum2_moe: the head is untied and there is no lm_head")
        logits = _head(
            params["lm_head"], params["final_norm"], h[at], dims["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions):
    return logits_and_near_ties(cfg, params, ids, score_positions)[0]
