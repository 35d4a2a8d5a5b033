"""Qwen3 dense decoder, plain ``jax.numpy`` in float32.

Written from the published description (Qwen3 technical report, and the
``Qwen3ForCausalLM`` config keys in ``configs/*.json``), independent of
``sutro_tpu/models/transformer.py``: no kernels, no cache, no batching.

    h = embed[ids]
    for each layer:
        x = RMSNorm(h) ; q, k, v = x Wq, x Wk, x Wv          (no biases)
        q, k = RMSNorm_per_head(q), RMSNorm_per_head(k)      (QK-norm)
        q, k = RoPE(q), RoPE(k)     (rotate-half, base rope_theta)
        a = softmax(q k^T / sqrt(head_dim), causal) v        (GQA: each
            KV head serves num_heads / num_kv_heads query heads)
        h = h + a Wo
        x = RMSNorm(h) ; h = h + (silu(x Wgate) * (x Wup)) Wdown
    logits = RMSNorm(h) W_head        (W_head = embed^T when tied)

Weights arrive in the layout the system serves them in (a dict with
``embed`` [V, H], ``final_norm`` [H], optional ``lm_head`` [H, V] and
``layers`` with every per-layer tensor stacked on a leading layer axis),
in whatever dtype; each layer is sliced out and up-cast to float32 as it
is used, so a 4B model's reference needs 0.4 GB a layer and fits beside
a full KV pool, and under a device mesh it runs on the sharded weights
as they are. The head is applied in vocabulary blocks, at the scored
positions only.

Departures from the description: none in the mathematics. Matrix
multiplications run under ``jax.default_matmul_precision("highest")``
(a TPU would otherwise run float32 matmuls in bf16 passes).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, N, Dh]; rotate-half: pairs (i, i + Dh/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_weight(layers: Dict[str, Any], index):
    """``w(name)``: layer ``index`` of a stacked weight, in float32."""

    def w(name):
        return jax.lax.dynamic_index_in_dim(
            layers[name], index, axis=0, keepdims=False
        ).astype(F32)

    return w


def attention(dims: Dict[str, Any], w, h, positions):
    """The attention half of a block: ``h + Attn(RMSNorm(h)) Wo`` over a
    whole sequence. ``h`` [T, H] float32; ``w(name)`` gives the layer's
    weight."""
    NH, KVH, Dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    eps, theta = dims["eps"], dims["theta"]
    T = h.shape[0]
    x = _rms(h, w("attn_norm"), eps)
    q = (x @ w("wq")).reshape(T, NH, Dh)
    k = (x @ w("wk")).reshape(T, KVH, Dh)
    v = (x @ w("wv")).reshape(T, KVH, Dh)
    q = _rope(_rms(q, w("q_norm"), eps), positions, theta)
    k = _rope(_rms(k, w("k_norm"), eps), positions, theta)
    group = NH // KVH
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("tnd,snd->nts", q, k) / jnp.sqrt(F32(Dh))
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    return h + attn.reshape(T, NH * Dh) @ w("wo")


def layer(dims: Dict[str, Any], layers: Dict[str, Any], index, h, positions):
    """One decoder block over a whole sequence. ``h`` [T, H] float32;
    ``layers`` holds the stacked weights, ``index`` picks the layer."""
    w = layer_weight(layers, index)
    h = attention(dims, w, h, positions)
    x = _rms(h, w("mlp_norm"), dims["eps"])
    return h + (jax.nn.silu(x @ w("w_gate")) * (x @ w("w_up"))) @ w("w_down")


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a published config's keys."""
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "heads": NH,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim") or H // NH),
        "layers": int(cfg["num_hidden_layers"]),
        "eps": float(cfg.get("rms_norm_eps", 1e-6)),
        "theta": float(cfg.get("rope_theta", 1e6)),
        "tied": bool(cfg.get("tie_word_embeddings", True)),
    }


def _vocab_blocks(vocab: int, limit: int = 32768) -> int:
    for nb in range(1, 257):
        if vocab % nb == 0 and vocab // nb <= limit:
            return nb
    return 1


def logits_at(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int],
):
    """Full causal forward of ``ids`` ([T] ints); float32 logits
    ``[len(score_positions), V]`` at those positions."""
    dims = dims_of(cfg)
    frozen = tuple(sorted(dims.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for index in range(dims["layers"]):
            h = _layer_jit(frozen, params["layers"], index, h, positions)
        tied = "lm_head" not in params
        head = params["embed"] if tied else params["lm_head"]
        return _head(head, params["final_norm"], h[at], dims["eps"], tied)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_jit(frozen_dims, layers, index, h, positions):
    return layer(dict(frozen_dims), layers, index, h, positions)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(head, final_norm, h, eps, tied):
    """``head`` is the embedding table [V, H] when tied, else [H, V]."""
    x = _rms(h, final_norm.astype(F32), eps)
    vocab = head.shape[0] if tied else head.shape[1]
    nb = _vocab_blocks(vocab)
    size = vocab // nb
    outs = []
    for b in range(nb):
        if tied:
            blk = jax.lax.dynamic_slice_in_dim(head, b * size, size, 0)
            outs.append(x @ blk.astype(F32).T)
        else:
            blk = jax.lax.dynamic_slice_in_dim(head, b * size, size, 1)
            outs.append(x @ blk.astype(F32))
    return jnp.concatenate(outs, axis=-1)
