"""Laguna S 2.1 decoder (``model_type`` ``laguna``), plain ``jax.numpy``
in float32.

Written from the published ``config.json`` keys of poolside/Laguna-S-2.1
as ISSUE 61 reads them, independent of ``sutro_tpu/``: no kernels, no
cache, no pages, no batching, every expert in turn. ``h`` is the
residual stream, ``l`` the layer, ``kind = layer_types[l]``,
``H_l = num_attention_heads_per_layer[l]`` (48 in a full layer, 72 in a
sliding one; both over ``num_key_value_heads`` = 8 KV heads):

    h = embed[ids]
    layer l:   u = RMSNorm(h)
               q = u Wq_l  [T, H_l, 128];  k, v = u Wk_l, u Wv_l  [T, 8, 128]
               q, k = RMSNorm_per_head(q), RMSNorm_per_head(k)    (ASSUMED)
               q, k = Rotary_kind(q), Rotary_kind(k)
               s = q k^T / sqrt(head_dim), causal; on a
                   "sliding_attention" layer only keys with
                   q_pos - k_pos < sliding_window
               o = softmax(s) v                    [T, H_l, 128]
               g = sigmoid(u Wg_l)                 [T, H_l]       (ASSUMED form)
               h = h + (o * g[..., None]) Wo_l     (gating per-head)
               x = RMSNorm(h)
               h = h + FFN_l(x)
    logits = RMSNorm(h) W_head            (untied)

    Rotary_kind turns the FIRST r = partial_rotary_factor x head_dim
    elements of a head, rotate-half INSIDE them (pairs (j, j + r/2), angle
    pos * f_j), and passes the other head_dim - r through:
      "sliding_attention" (rope_type default, factor 1): r = 128,
          f_j = theta^(-2j/r), theta 10,000
      "full_attention" (rope_type yarn, factor 0.5): r = 64, theta 500,000,
          e_j = theta^(-2j/r)            extrapolation
          n_j = e_j / factor             interpolation
          c(t) = r ln(original / (2 pi t)) / (2 ln theta)
          low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), r - 1)
          ramp_j = clip((j - low) / (high - low), 0, 1)
          f_j = n_j ramp_j + e_j (1 - ramp_j)
          cos and sin times attention_factor, TAKEN FROM THE FILE

    FFN_l: "dense" (mlp_only_layers: layer 0): SwiGLU of intermediate_size.
           "sparse": p = softmax(x Wr) over ALL num_experts (ASSUMED: no
           scoring key); the num_experts_per_tok largest, divided by their
           sum (norm_topk_prob), times moe_routed_scaling_factor, weigh
           the chosen experts' OUTPUTS (moe_apply_router_weight_on_input
           false; no cap on the logits: moe_router_logit_softcapping 0);
           experts SwiGLU of moe_intermediate_size; plus ONE shared SwiGLU
           expert of shared_expert_intermediate_size for every token,
           times sigmoid(x w_s), w_s [H, 1] (ASSUMED: Qwen2-MoE's shared
           expert gate, whose key set num_experts / norm_topk_prob /
           decoder_sparse_step / mlp_only_layers /
           shared_expert_intermediate_size is).

**The share.** As ``kda_gqa_moe`` / ``mla_moe``: the file's ``share``
(``experts_published``, ``first_expert``, ``experts_held``) says which
experts are HELD. The router keeps its ``experts_published`` outputs and
its top-k over all of them; the chosen experts that are not held are
left out as the system leaves them out; the shared expert (with its
gate) is whole. ``logits_and_near_ties(..., experts=(first, count))``
is another share of the same stack and ``shared=False`` leaves the
shared expert out: the tests add the shares up. A sliced vocabulary is a
smaller vocabulary.

Attention is computed a block of ``QUERY_BLOCK`` queries at a time
against all keys, so that 7,416 positions fit (a block's scores are
heads x 256 x T float32); the numbers are those of the one product.

Weights arrive in the layout the system serves them in, stacked per
kind of layer: ``layers["attn"]`` (the full_attention layers:
``attn_norm``, ``wq`` [L_full, H, 48 x 128], ``wk``, ``wv``, ``wo``,
``q_norm``, ``k_norm``, ``w_attn_gate`` [L_full, H, 48]),
``layers["swa"]`` (the sliding_attention layers, the same names at 72
heads), ``layers["dense"]`` (``mlp_norm``, ``w_gate``, ``w_up``,
``w_down``) and ``layers["moe"]`` (``mlp_norm``, ``router`` [L, H, E],
``we_gate``, ``we_up`` [L, E_held, H, F], ``we_down`` [L, E_held, F, H],
``shared_gate``, ``shared_up``, ``shared_down``, ``shared_expert_gate``
[L, H, 1]); layer l's weights are its kind's next in order.

Assumed, and said so in ``laguna_moe.md`` and the configuration file:
(1) the gate's form, (2) softmax over all experts, (3) the shared
expert's gate, (4) the per-head RMSNorm of q and k; no
multi-token-prediction module and no tower (no key for either).

Controls (``variant``; the tools'): ``"rotary_whole_head"`` turns all
128 elements of a full layer's heads (frequencies of 128 elements),
``"no_head_gate"`` leaves the gate out, ``"no_shared_gate"`` the shared
expert's. A system that implements the description must FAIL against
each.

What it refuses rather than guesses: see ``dims_of``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .qwen3_dense import F32, _embed, _head, _rms, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
KINDS = {"sliding_attention": "swa", "full_attention": "attn"}
FFNS = {"dense": "dense", "sparse": "moe"}
QUERY_BLOCK = 256
VARIANTS = (None, "rotary_whole_head", "no_head_gate", "no_shared_gate")

__all__ = [
    "ROUTED", "TIE_MARGIN", "VARIANTS", "dims_of", "logits_at",
    "logits_and_near_ties",
]


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    types = tuple(cfg["layer_types"])
    ffns = tuple(cfg["mlp_layer_types"])
    heads = tuple(int(n) for n in cfg["num_attention_heads_per_layer"])
    depth = int(cfg["num_hidden_layers"])
    rope = cfg["rope_parameters"]
    plain, yarn = rope["sliding_attention"], rope["full_attention"]
    kv_heads = int(cfg["num_key_value_heads"])
    dense_at = tuple(int(i) for i in cfg.get("mlp_only_layers") or ())
    refuse = {
        "layer_types entries other than sliding_attention / full_attention":
            bool(set(types) - set(KINDS)),
        "mlp_layer_types entries other than dense / sparse":
            bool(set(ffns) - set(FFNS)),
        "lists that are not one entry a layer": not (
            len(types) == len(ffns) == len(heads) == depth
            == len(cfg.get("gating_types") or types)
        ),
        "mlp_only_layers that disagree with mlp_layer_types": dense_at != tuple(
            i for i, f in enumerate(ffns) if f == "dense"
        ),
        "a gating other than per-head": cfg.get("gating") != "per-head" or set(
            cfg.get("gating_types") or ["per_head"]
        ) != {"per_head"},
        "query heads that are no multiple of the KV heads":
            any(n % kv_heads for n in heads),
        "attention_bias true": bool(cfg.get("attention_bias")),
        "a tied head": bool(cfg.get("tie_word_embeddings")),
        "decoder_sparse_step != 1": int(cfg.get("decoder_sparse_step", 1)) != 1,
        "norm_topk_prob false": cfg.get("norm_topk_prob") is not True,
        "moe_apply_router_weight_on_input true":
            bool(cfg.get("moe_apply_router_weight_on_input")),
        "a cap on the router's logits":
            float(cfg.get("moe_router_logit_softcapping") or 0) != 0.0,
        "no shared expert":
            int(cfg.get("shared_expert_intermediate_size") or 0) < 1,
        "rope types other than default (sliding) and yarn (full)":
            plain.get("rope_type") != "default"
            or yarn.get("rope_type") != "yarn",
        "a yarn section without attention_factor":
            "attention_factor" not in yarn,
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference laguna_moe does not implement: {', '.join(bad)}"
        )
    share = cfg.get("share") or {}
    experts = int(share.get("experts_published", cfg["num_experts"]))
    head_dim = int(cfg["head_dim"])

    def rotary_width(section) -> int:
        r = float(section.get("partial_rotary_factor", 1)) * head_dim
        if r != int(r) or int(r) % 2 or not 2 <= r <= head_dim:
            raise ValueError(
                f"laguna_moe: partial_rotary_factor gives a rotary part of "
                f"{r} elements of a head of {head_dim}"
            )
        return int(r)

    dims = {
        "types": types, "ffns": ffns, "heads": heads, "kv_heads": kv_heads,
        "head_dim": head_dim, "eps": float(cfg["rms_norm_eps"]),
        "window": int(cfg["sliding_window"]),
        "rot_window": rotary_width(plain),
        "rot_full": rotary_width(yarn),
        "theta_window": float(plain["rope_theta"]),
        "theta_full": float(yarn["rope_theta"]),
        "yarn_factor": float(yarn["factor"]),
        "yarn_original": float(yarn["original_max_position_embeddings"]),
        "yarn_beta_fast": float(yarn["beta_fast"]),
        "yarn_beta_slow": float(yarn["beta_slow"]),
        "yarn_attention_factor": float(yarn["attention_factor"]),
        "experts": experts,
        "first": int(share.get("first_expert", 0)),
        "held": int(share.get("experts_held", experts)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["moe_routed_scaling_factor"]),
    }
    if not 1 <= dims["top_k"] <= experts:
        raise ValueError("laguna_moe: num_experts_per_tok outside 1..experts")
    if dims["first"] + dims["held"] > experts:
        raise ValueError("laguna_moe: the held experts are not the router's")
    return dims


def inverse_frequencies(d: Dict[str, Any], kind: str, r: int):
    """``(f [r / 2] float32, what cos and sin are multiplied by)`` of a
    rotary part of ``r`` elements in a layer of ``kind``."""
    j = np.arange(r // 2, dtype=np.float64)
    if kind == "swa":
        return (d["theta_window"] ** (-2.0 * j / r)).astype(np.float32), 1.0
    theta = d["theta_full"]
    extrapolation = theta ** (-2.0 * j / r)
    interpolation = extrapolation / d["yarn_factor"]

    def correction(turns: float) -> float:
        return r * math.log(
            d["yarn_original"] / (2.0 * math.pi * turns)
        ) / (2.0 * math.log(theta))

    low = max(math.floor(correction(d["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(d["yarn_beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    f = interpolation * ramp + extrapolation * (1.0 - ramp)
    return f.astype(np.float32), d["yarn_attention_factor"]


def rotary(x, positions, f, scale):
    """x [T, N, Dh]: its first ``2 len(f)`` elements turned, rotate-half
    inside them, angles ``positions * f``; the rest as they are."""
    half = len(f)
    ang = positions.astype(F32)[:, None] * jnp.asarray(f)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def attention(d: Dict[str, Any], kind: str, NH: int, w, h, positions,
              variant: Optional[str] = None):
    """``h + (Attn(u) * gate(u)) Wo`` over a whole sequence [T, H], for
    a layer of ``kind`` with ``NH`` query heads."""
    KVH, Dh, eps = d["kv_heads"], d["head_dim"], d["eps"]
    T = h.shape[0]
    r = d["rot_window"] if kind == "swa" else d["rot_full"]
    if variant == "rotary_whole_head":
        r = Dh
    f, scale = inverse_frequencies(d, kind, r)
    u = _rms(h, w("attn_norm"), eps)
    q = (u @ w("wq")).reshape(T, NH, Dh)
    k = (u @ w("wk")).reshape(T, KVH, Dh)
    v = (u @ w("wv")).reshape(T, KVH, Dh)
    q = rotary(_rms(q, w("q_norm"), eps), positions, f, scale)
    k = rotary(_rms(k, w("k_norm"), eps), positions, f, scale)
    group = NH // KVH
    k = jnp.repeat(k, group, axis=1)     # query head n reads KV head n // group
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for lo in range(0, T, QUERY_BLOCK):
        qb, qp = q[lo : lo + QUERY_BLOCK], positions[lo : lo + QUERY_BLOCK]
        scores = jnp.einsum("tnd,snd->nts", qb, k) / jnp.sqrt(F32(Dh))
        seen = qp[:, None] >= positions[None, :]
        if kind == "swa":
            seen = seen & (qp[:, None] - positions[None, :] < d["window"])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(
            jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
        )
    o = jnp.concatenate(outs) if len(outs) > 1 else outs[0]   # [T, NH, Dh]
    if variant != "no_head_gate":
        o = o * jax.nn.sigmoid(u @ w("w_attn_gate"))[:, :, None]
    return h + o.reshape(T, NH * Dh) @ w("wo")


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(d: Dict[str, Any], logits):
    """``logits`` [T, E] float32 -> (gates [T, E], zero off the chosen
    experts; gap [T] between the k-th and (k+1)-th logit in standard
    deviations of the token's logits, inf when every expert is chosen)."""
    E, K = d["experts"], d["top_k"]
    T = logits.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True) * d["scale"]
    gates = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_e].set(top_p)
    if K == E:
        return gates, jnp.full((T,), jnp.inf, F32)
    ranked = jax.lax.top_k(logits, K + 1)[0]
    gap = (ranked[:, K - 1] - ranked[:, K]) / jnp.std(logits, axis=-1)
    return gates, gap


def routed_ffn(d: Dict[str, Any], moe: Dict[str, Any], index, x,
               shared: bool = True, variant: Optional[str] = None):
    """Routed layer ``index`` over normed ``x`` [T, H]: (the held
    experts' weighted outputs + the gated shared expert [T, H], gap
    [T]). Expert j of the stack is the router's expert ``first + j``."""
    w = layer_weight(moe, index)
    gates, gap = route(d, x @ w("router"))

    def add_expert(j, acc):
        def we(name):
            stack = moe[name]
            return jax.lax.dynamic_slice(
                stack, (index, j, 0, 0), (1, 1) + stack.shape[2:]
            )[0, 0].astype(F32)

        y = swiglu(x, we("we_gate"), we("we_up"), we("we_down"))
        g = jax.lax.dynamic_slice_in_dim(gates, d["first"] + j, 1, axis=1)
        return acc + g * y

    out = jax.lax.fori_loop(0, d["held"], add_expert, jnp.zeros_like(x))
    if shared:
        y = swiglu(x, w("shared_gate"), w("shared_up"), w("shared_down"))
        if variant != "no_shared_gate":
            y = y * jax.nn.sigmoid(x @ w("shared_expert_gate"))
        out = out + y
    return out, gap


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 8, 9, 10))
def _layer_jit(frozen, kind, NH, ffn, layers, m_index, f_index, h, shared,
               variant, part, positions):
    """One layer over a whole sequence: (h [T, H], gap [T]; inf for a
    dense layer). ``part``: "both", or "ffn" alone (the tests': a
    layer's FFN output added to a given input)."""
    d = dict(frozen)
    if part != "ffn":
        h = attention(
            d, kind, NH, layer_weight(layers[kind], m_index), h, positions,
            variant,
        )
    w = layer_weight(layers[ffn], f_index)
    x = _rms(h, w("mlp_norm"), d["eps"])
    if ffn == "dense":
        y = swiglu(x, w("w_gate"), w("w_up"), w("w_down"))
        return h + y, jnp.full((h.shape[0],), jnp.inf, F32)
    y, gap = routed_ffn(d, layers["moe"], f_index, x, shared, variant)
    return h + y, gap


def _held(params, experts: Optional[Tuple[int, int]], d):
    """``params`` with the routed stacks cut to experts ``experts`` =
    (first, count) OF THE STACK HANDED IN (whose first is the file's
    ``first_expert``), and ``d`` saying so."""
    if experts is None:
        return params, d
    first, count = experts
    moe = dict(params["layers"]["moe"])
    for name in ("we_gate", "we_up", "we_down"):
        moe[name] = moe[name][:, first : first + count]
    layers = dict(params["layers"], moe=moe)
    return dict(params, layers=layers), dict(
        d, first=d["first"] + first, held=count
    )


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], *,
    experts: Optional[Tuple[int, int]] = None, shared: bool = True,
    variant: Optional[str] = None, return_hidden: bool = False,
    part: str = "both",
):
    """Full causal forward of ``ids`` ([T] ints), a layer at a time:
    float32 logits ``[len(score_positions), V]`` and, per scored
    position, the number of routed layers whose selection there was a
    near tie. ``experts``, ``shared``, ``part`` are the tests' (a share
    of the stack's experts, the shared expert left out, the FFNs alone);
    ``variant`` the controls' (module docstring). ``return_hidden`` gives
    the residual stream before the final norm in the logits' place."""
    if variant not in VARIANTS:
        raise ValueError(f"laguna_moe: variant {variant!r} not in {VARIANTS}")
    d = dims_of(cfg)
    params, d = _held(params, experts, d)
    types, ffns, heads = d.pop("types"), d.pop("ffns"), d.pop("heads")
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    seen = {"attn": 0, "swa": 0, "dense": 0, "moe": 0}
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for name, ffn_name, NH in zip(types, ffns, heads):
            kind, ffn = KINDS[name], FFNS[ffn_name]
            h, gap = _layer_jit(
                frozen, kind, NH, ffn, params["layers"], seen[kind],
                seen[ffn], h, shared, variant, part, positions,
            )
            seen[kind] += 1
            seen[ffn] += 1
            ties = ties + (gap[at] < TIE_MARGIN)
        if return_hidden:
            return h[at], ties
        if "lm_head" not in params:
            raise ValueError("laguna_moe: the head is untied and there is no lm_head")
        logits = _head(
            params["lm_head"], params["final_norm"], h[at], d["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions, **kw):
    return logits_and_near_ties(cfg, params, ids, score_positions, **kw)[0]
