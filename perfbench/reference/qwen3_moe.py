"""Qwen3-MoE decoder, plain ``jax.numpy`` in float32.

Written from the published description (Qwen3 technical report; the
``Qwen3MoeForCausalLM`` config keys), independent of
``sutro_tpu/models/transformer.py`` and ``sutro_tpu/ops/moe.py``: no
kernels, no cache, no batching, no sort, no grouped product, no
capacity. Attention, embedding and head are the dense Qwen3 reference's
(``qwen3_dense.py``); only the MLP differs:

    x = RMSNorm(h)
    r = x W_router                      float32 logits over ALL experts
    p = softmax(r)                      over all experts
    (p_1..p_k, e_1..e_k) = the k largest of p        (num_experts_per_tok)
    p_i = p_i / sum_j p_j               when norm_topk_prob
    h = h + sum_i p_i (silu(x Wgate[e_i]) * (x Wup[e_i])) Wdown[e_i]

Every expert's SwiGLU is computed in turn, one expert's three matrices
sliced out of the stack and up-cast at a time (19 MB for Qwen3-30B-A3B),
and added with the token's weight for it, which is zero for an expert
the token did not choose: the same sum as above, with no gather of
weights by token.

Weights arrive in the layout the system serves them in: ``layers``
holds, beside the attention tensors, ``router`` [L, H, E], ``we_gate``
and ``we_up`` [L, E, H, F], ``we_down`` [L, E, F, H].

What it refuses rather than guesses: ``mlp_only_layers`` or a
``decoder_sparse_step`` other than "every layer routed", a non-zero
``shared_expert_intermediate_size``, and a configuration without
``norm_topk_prob`` (the library's default is false, Qwen3's published
files say true, OLMoE's say false).

A routed model cannot be held to the float32 reference position by
position (``README.md`` here): where a token's k-th and (k+1)-th router
logits are closer than the system's rounding moves them, the system and
the reference choose different experts, and that position's logits
differ by far more than rounding. ``ROUTED`` tells
``correctness.numbers`` to apply the routed rule, and
``logits_and_near_ties`` counts, for each scored position, the layers at
which this reference's own k-th and (k+1)-th logits are within
``TIE_MARGIN`` (in units of the standard deviation of that token's
logits over the experts), so a run's facts show how many of its
positions were exposed.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from .qwen3_dense import (
    F32, _embed, _head, _rms, attention, dims_of, layer_weight,
)

ROUTED = True
TIE_MARGIN = 0.02


def moe_dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    if cfg.get("mlp_only_layers"):
        raise ValueError("qwen3_moe: mlp_only_layers is not empty")
    if int(cfg.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("qwen3_moe: decoder_sparse_step is not 1")
    if int(cfg.get("shared_expert_intermediate_size") or 0):
        raise ValueError("qwen3_moe: a shared expert is not described here")
    if "norm_topk_prob" not in cfg:
        raise ValueError("qwen3_moe: the configuration must state norm_topk_prob")
    dims = dims_of(cfg)
    dims.update(
        experts=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
    )
    if not 1 <= dims["top_k"] <= dims["experts"]:
        raise ValueError("qwen3_moe: num_experts_per_tok outside 1..num_experts")
    return dims


def route(dims: Dict[str, Any], logits):
    """``logits`` [T, E] float32 -> (gates [T, E], zero off the chosen
    experts; gap [T] between the k-th and (k+1)-th logit, in standard
    deviations of the token's logits, inf when every expert is chosen)."""
    E, K = dims["experts"], dims["top_k"]
    T = logits.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    if dims["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gates = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_e].set(top_p)
    if K == E:
        return gates, jnp.full((T,), jnp.inf, F32)
    ranked = jax.lax.top_k(logits, K + 1)[0]
    gap = (ranked[:, K - 1] - ranked[:, K]) / jnp.std(logits, axis=-1)
    return gates, gap


def routed_mlp(dims: Dict[str, Any], layers: Dict[str, Any], index, x):
    """The routed MLP of layer ``index`` over normed ``x`` [T, H]:
    (sum of the chosen experts' weighted SwiGLUs [T, H], gap [T])."""
    gates, gap = route(dims, x @ layer_weight(layers, index)("router"))

    def add_expert(e, acc):
        def we(name):
            stack = layers[name]
            return jax.lax.dynamic_slice(
                stack, (index, e, 0, 0), (1, 1) + stack.shape[2:]
            )[0, 0].astype(F32)

        y = (jax.nn.silu(x @ we("we_gate")) * (x @ we("we_up"))) @ we("we_down")
        return acc + jax.lax.dynamic_slice_in_dim(gates, e, 1, axis=1) * y

    out = jax.lax.fori_loop(0, dims["experts"], add_expert, jnp.zeros_like(x))
    return out, gap


def layer(dims: Dict[str, Any], layers: Dict[str, Any], index, h, positions):
    """One routed block over a whole sequence: (h [T, H], gap [T])."""
    w = layer_weight(layers, index)
    h = attention(dims, w, h, positions)
    y, gap = routed_mlp(dims, layers, index, _rms(h, w("mlp_norm"), dims["eps"]))
    return h + y, gap


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_jit(frozen_dims, layers, index, h, positions):
    return layer(dict(frozen_dims), layers, index, h, positions)


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int],
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of layers whose routing there was a near tie."""
    dims = moe_dims_of(cfg)
    frozen = tuple(sorted(dims.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for index in range(dims["layers"]):
            h, gap = _layer_jit(frozen, params["layers"], index, h, positions)
            ties = ties + (gap[at] < TIE_MARGIN)
        tied = "lm_head" not in params
        head = params["embed"] if tied else params["lm_head"]
        logits = _head(head, params["final_norm"], h[at], dims["eps"], tied)
    return logits, ties


def logits_at(cfg, params, ids, score_positions):
    return logits_and_near_ties(cfg, params, ids, score_positions)[0]
