"""LFM2-MoE decoder (``model_type`` ``lfm2_moe``), plain ``jax.numpy``
in float32.

Written from the published description of the ``lfm2_moe`` model code and
its ``config.json`` keys, independent of ``sutro_tpu/``: no kernels, no
cache (the short convolution is a causal convolution over the whole
sequence), no batching, no sort, no grouped product, every expert in
turn. Embedding, head, RMSNorm and the attention block are the dense
Qwen3 reference's (``qwen3_dense.py``): QK-RMSNorm per head, rotate-half
RoPE, causal GQA. ``h`` is the residual stream:

    layer i:   u = RMSNorm(h; operator_norm_i)
               h = h + Mixer_i(u)          Mixer_i by layer_types[i]
               x = RMSNorm(h; ffn_norm_i)
               h = h + FFN_i(x)            dense for i < num_dense_layers, else routed
    after the last layer: RMSNorm, then the head (tied to the embedding)

    conv mixer ("conv"), K = conv_L_cache, depthwise taps w_conv [H, K]:
               [B | C | z] = u W_in        thirds of 3H, in THIS order
               g_t = B_t * z_t
               c_t = sum_{j<K} w_conv[:, j] * g_{t-(K-1)+j}     g_s = 0 for s < 0
               Mixer(u)_t = (C_t * c_t) W_out

    routed FFN: r = x W_g                  float32 logits over all experts
               s = sigmoid(r)
               S = the num_experts_per_tok experts with the largest s + b
               p_e = s_e / (sum_{e' in S} s_e' + 1e-6)   (norm_topk_prob)
               p_e = p_e * routed_scaling_factor
               FFN(x) = sum_{e in S} p_e (silu(x W1_e) * (x W3_e)) W2_e

``b`` (``expert_bias``) chooses and never weighs. Weights arrive in the
layout the system serves them in, stacked per kind of layer:
``layers["attn"]`` (``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``,
``q_norm``, ``k_norm``; [L_attn, ...]), ``layers["conv"]``
(``attn_norm``, ``w_in`` [L_conv, H, 3H], ``w_conv`` [L_conv, H, K],
``w_out`` [L_conv, H, H]), ``layers["dense"]`` (``mlp_norm``, ``w_gate``,
``w_up``, ``w_down``) and ``layers["moe"]`` (``mlp_norm``, ``router``
[L_moe, H, E], ``router_bias`` [L_moe, E], ``we_gate``, ``we_up``
[L_moe, E, H, F], ``we_down`` [L_moe, E, F, H]); layer i's weights are
its kind's next in order.

Departures from the published code, each deliberate:
- the published conv is a ``Conv1d`` over a left-padded sequence; here
  it is the K-term sum above, the same numbers;
- ``w_conv`` is [H, K] (the published weight is [H, 1, K]);
- the projections are stored input-major ([in, out]), the published
  ``Linear`` weights output-major;
- the head is tied to the embedding by assumption (the configuration
  file says so under ``assumed``).

What it refuses rather than guesses: ``conv_bias`` true, a
``layer_types`` entry other than ``conv`` and ``full_attention``, a
``use_expert_bias`` that is not true (the plain top-k branch is not
written here), a configuration without ``norm_topk_prob``.

``ROUTED`` sends ``correctness.numbers`` to the routed rule. Near ties
are measured on ``s + b``, the quantity that selects: the gap between
its k-th and (k+1)-th largest, in standard deviations of the token's
``s + b`` over the experts, under ``TIE_MARGIN``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from .qwen3_dense import F32, _embed, _head, _rms, attention, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
KINDS = {"conv": "conv", "full_attention": "attn"}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    if cfg.get("conv_bias"):
        raise ValueError("lfm2_moe: conv_bias true is not described here")
    if cfg.get("use_expert_bias") is not True:
        raise ValueError("lfm2_moe: use_expert_bias must be true")
    if "norm_topk_prob" not in cfg:
        raise ValueError("lfm2_moe: the configuration must state norm_topk_prob")
    types = tuple(cfg["layer_types"])
    unknown = sorted(set(types) - set(KINDS))
    if unknown:
        raise ValueError(f"lfm2_moe: layer_types entries {unknown} are not described here")
    L = int(cfg["num_hidden_layers"])
    if len(types) != L:
        raise ValueError(f"lfm2_moe: {len(types)} layer_types for {L} layers")
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    rope = cfg.get("rope_parameters") or {}
    dims = {
        "heads": NH,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim") or H // NH),
        "layers": L,
        "types": types,
        "dense_layers": int(cfg["num_dense_layers"]),
        "taps": int(cfg["conv_L_cache"]),
        "eps": float(cfg["norm_eps"]),
        "theta": float(rope.get("rope_theta", cfg.get("rope_theta", 1e6))),
        "experts": int(cfg["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg.get("routed_scaling_factor", 1.0)),
    }
    if not 1 <= dims["top_k"] <= dims["experts"]:
        raise ValueError("lfm2_moe: num_experts_per_tok outside 1..num_experts")
    return dims


def conv_mixer(dims: Dict[str, Any], w, h):
    """``h + Conv(RMSNorm(h))`` over a whole sequence [T, H]."""
    T, H = h.shape
    K = dims["taps"]
    u = _rms(h, w("attn_norm"), dims["eps"])
    bcz = u @ w("w_in")
    b, c, z = bcz[:, :H], bcz[:, H : 2 * H], bcz[:, 2 * H :]
    g = jnp.concatenate([jnp.zeros((K - 1, H), F32), b * z])
    taps = w("w_conv")
    conv = sum(g[j : j + T] * taps[:, j] for j in range(K))
    return h + (c * conv) @ w("w_out")


def route(dims: Dict[str, Any], logits, bias):
    """``logits`` [T, E] float32, ``bias`` [E] -> (gates [T, E], zero
    off the chosen experts; gap [T] between the k-th and (k+1)-th of
    ``s + b``, in its standard deviations, inf when every expert is
    chosen)."""
    E, K = dims["experts"], dims["top_k"]
    T = logits.shape[0]
    s = jax.nn.sigmoid(logits)
    chosen_by = s + bias
    top_e = jax.lax.top_k(chosen_by, K)[1]
    p = jnp.take_along_axis(s, top_e, axis=-1)
    if dims["norm_topk"]:
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-6)
    p = p * dims["scale"]
    gates = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_e].set(p)
    if K == E:
        return gates, jnp.full((T,), jnp.inf, F32)
    ranked = jax.lax.top_k(chosen_by, K + 1)[0]
    gap = (ranked[:, K - 1] - ranked[:, K]) / jnp.std(chosen_by, axis=-1)
    return gates, gap


def routed_ffn(dims: Dict[str, Any], moe: Dict[str, Any], index, x):
    """Routed layer ``index`` (among the routed ones) over normed ``x``
    [T, H]: (the chosen experts' weighted SwiGLUs [T, H], gap [T])."""
    w = layer_weight(moe, index)
    gates, gap = route(dims, x @ w("router"), w("router_bias"))

    def add_expert(e, acc):
        def we(name):
            stack = moe[name]
            return jax.lax.dynamic_slice(
                stack, (index, e, 0, 0), (1, 1) + stack.shape[2:]
            )[0, 0].astype(F32)

        y = (jax.nn.silu(x @ we("we_gate")) * (x @ we("we_up"))) @ we("we_down")
        return acc + jax.lax.dynamic_slice_in_dim(gates, e, 1, axis=1) * y

    out = jax.lax.fori_loop(0, dims["experts"], add_expert, jnp.zeros_like(x))
    return out, gap


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(frozen_dims, mixer, ffn, layers, m_index, f_index, h, positions):
    """One block over a whole sequence: (h [T, H], gap [T], inf for a
    dense layer)."""
    dims = dict(frozen_dims)
    w = layer_weight(layers[mixer], m_index)
    if mixer == "conv":
        h = conv_mixer(dims, w, h)
    else:
        h = attention(dims, w, h, positions)
    f = layer_weight(layers[ffn], f_index)
    x = _rms(h, f("mlp_norm"), dims["eps"])
    if ffn == "dense":
        y = (jax.nn.silu(x @ f("w_gate")) * (x @ f("w_up"))) @ f("w_down")
        return h + y, jnp.full((h.shape[0],), jnp.inf, F32)
    y, gap = routed_ffn(dims, layers["moe"], f_index, x)
    return h + y, gap


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int],
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of routed layers whose selection there was a near tie."""
    dims = dims_of(cfg)
    frozen = tuple(sorted(dims.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    seen = {"attn": 0, "conv": 0, "dense": 0, "moe": 0}
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for i, kind in enumerate(dims["types"]):
            mixer = KINDS[kind]
            ffn = "dense" if i < dims["dense_layers"] else "moe"
            h, gap = _layer_jit(
                frozen, mixer, ffn, params["layers"], seen[mixer], seen[ffn],
                h, positions,
            )
            seen[mixer] += 1
            seen[ffn] += 1
            ties = ties + (gap[at] < TIE_MARGIN)
        if "lm_head" in params:
            raise ValueError("lfm2_moe: an untied head is not described here")
        logits = _head(params["embed"], params["final_norm"], h[at], dims["eps"], True)
    return logits, ties


def logits_at(cfg, params, ids, score_positions):
    return logits_and_near_ties(cfg, params, ids, score_positions)[0]
