"""Latent-attention decoder with routed layers (``model_type``
``joyai_llm_flash``: JoyAI-LLM-Flash; DeepSeek-V3's key set), plain
``jax.numpy`` in float32.

Written from the published ``config.json`` keys and the family's
published description (DeepSeek-V2, "Multi-head Latent Attention";
DeepSeek-V3, the sigmoid router with a selection bias), independent of
``sutro_tpu/``: no kernels, no cache, no batching, no absorbed products,
no sort of rows by expert. The EXPANDED form only: every head's K and V
are rebuilt from the latent values at every position of a full causal
forward. ``h`` is the residual stream:

    h = embed[ids]
    layer i:   h = h + attn_i(RMSNorm(h)) ;  h = h + ffn_i(RMSNorm(h))
    logits = RMSNorm(h) lm_head                                (untied)

    attn (every layer), NH heads, u [T, H] normed:
        c_q  = RMSNorm(u W_qa) * q_norm              q_lora_rank wide
        q    = c_q W_qb -> [T, NH, nope + rope]      [q_nope | q_pe]
        u W_kva -> [T, kv_lora_rank + rope]          [c_kv | k_pe]
        c_kv = RMSNorm(c_kv) * kv_norm ;  k_pe ONE vector a token
        rope (q_pe and k_pe only, theta rope_theta, no scaling): the
             pair (2i, 2i+1) turns by pos * theta^(-2i / rope)
             (``rope_interleave`` true)
        c_kv W_kvb -> [T, NH, nope + v_head_dim]     [k_nope | v]
        score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s))
                        / sqrt(nope + rope),    s <= t, softmax over s
        attn = concat_h(sum_s p_h(t, s) v_h(s)) W_o

    ffn, layers 0 .. first_k_dense_replace - 1:  dense SwiGLU,
        (silu(u W_gate) * (u W_up)) W_down       width intermediate_size
    ffn, every later layer (moe_layer_freq 1):
        s = sigmoid(u W_router)                  float32, E_pub wide
        chosen = top-k of (s + e_score_correction_bias)
                 (topk_method noaux_tc; n_group = topk_group = 1: no
                 group limit)
        p = s[chosen] / (sum s[chosen] + 1e-20)   (norm_topk_prob)
        p = p * routed_scaling_factor
        expert_e(u) = (silu(u G_e) * (u U_e)) D_e   width moe_intermediate_size
        ffn(u) = sum_{e chosen} p_e expert_e(u) + shared(u)
        shared: ONE SwiGLU expert of n_shared_experts x
                moe_intermediate_size on every token, unweighted

**The share.** The configuration's file may state a chip's share of a
deployment (``share``: ``experts_published``, ``first_expert``): the
router keeps ``experts_published`` outputs and its top-k, and the sum
over the chosen experts runs over those that are HELD here, experts
``first_expert .. first_expert + n_routed_experts`` (the file's
``n_routed_experts`` counts the held ones). What the absent experts
would add is left out, as the system leaves it out; attention, the dense
layer and the shared expert are computed whole.
``logits_and_near_ties(..., experts=(first, count))`` takes another
share of the same weights: the tests add the shares up.

Weights arrive in the layout the system serves them in, stacked per kind:
``layers["mla"]`` (``attn_norm`` [L, H], ``w_qa`` [L, H, Rq], ``q_norm``
[L, Rq], ``w_qb`` [L, Rq, NH * (nope + rope)] a head's columns [q_nope |
q_pe], ``w_kva`` [L, H, Rkv + rope] columns [c_kv | k_pe], ``kv_norm``
[L, Rkv], ``w_kvb`` [L, Rkv, NH * (nope + v)] a head's columns [k_nope |
v], ``wo`` [L, NH * v, H]), ``layers["dense"]`` (``mlp_norm``,
``w_gate``, ``w_up``, ``w_down``) and ``layers["moe"]`` (``mlp_norm``,
``router`` [L_e, H, E_pub], ``router_bias`` [L_e, E_pub], ``we_gate``,
``we_up`` [L_e, E_held, H, F], ``we_down`` [L_e, E_held, F, H],
``shared_gate``, ``shared_up`` [L_e, H, F], ``shared_down`` [L_e, F,
H]); layer i's FFN weights are its kind's next in order.

Departures from the published description: the multi-token-prediction
block (``num_nextn_predict_layers``) is not computed: it is no part of
the next-token logits and the published inference code does not run it;
projections are stored input-major. Attention goes a block of queries at
a time over all earlier keys (the same sums: a sequence of thousands
would otherwise hold [NH, T, T] scores). ``rotary`` other than the
configuration's computes ANOTHER model (the tests' use).

What it refuses rather than guesses: ``rope_scaling`` other than null;
``rope_interleave`` false (unless a test asks); ``scoring_func`` other
than ``sigmoid``; ``topk_method`` other than ``noaux_tc``; ``n_group`` or
``topk_group`` other than 1; ``norm_topk_prob`` false; ``hidden_act``
other than ``silu``; ``attention_bias`` true; a tied head;
``moe_layer_freq`` other than 1; a ``q_lora_rank`` of null (the family's
members with a full-rank query).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .qwen3_dense import F32, _embed, _head, _rms, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
QUERY_BLOCK = 512


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    refuse = {
        "rope_scaling": cfg.get("rope_scaling") is not None,
        "rope_interleave false": cfg.get("rope_interleave") is not True,
        "scoring_func other than sigmoid": cfg.get("scoring_func") != "sigmoid",
        "topk_method other than noaux_tc": cfg.get("topk_method") != "noaux_tc",
        "n_group != 1": int(cfg.get("n_group", 1)) != 1,
        "topk_group != 1": int(cfg.get("topk_group", 1)) != 1,
        "norm_topk_prob false": cfg.get("norm_topk_prob") is not True,
        "hidden_act other than silu": cfg.get("hidden_act") != "silu",
        "attention_bias": bool(cfg.get("attention_bias")),
        "a tied head": bool(cfg.get("tie_word_embeddings", False)),
        "moe_layer_freq != 1": int(cfg.get("moe_layer_freq", 1)) != 1,
        "no q_lora_rank": not cfg.get("q_lora_rank"),
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference mla_moe does not implement: {', '.join(bad)}"
        )
    share = cfg.get("share") or {}
    held = int(cfg["n_routed_experts"])
    dims = {
        "layers": int(cfg["num_hidden_layers"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "heads": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "experts": int(share.get("experts_published", held)),
        "first": int(share.get("first_expert", 0)),
        "held": held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
    }
    if not 1 <= dims["top_k"] <= dims["experts"]:
        raise ValueError("mla_moe: num_experts_per_tok outside 1..experts")
    if dims["first"] + dims["held"] > dims["experts"]:
        raise ValueError("mla_moe: the held experts are not among the router's")
    return dims


def rope_interleaved(x, positions, theta):
    """x [T, ..., D]: the pair (2i, 2i+1) turns by pos * theta^(-2i/D)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = positions.astype(F32)[:, None] * inv[None, :]       # [T, D/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (D // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def rope_half_split(x, positions, theta):
    """The OTHER pairing, (i, i + D/2): not this model's; the tests
    hold the system apart from it."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


ROTARY = {"interleaved": rope_interleaved, "half_split": rope_half_split}


def latents(d: Dict[str, Any], w, u, positions, rotary: str = "interleaved"):
    """What the description says a token leaves behind: ``(c_kv [T, Rkv]
    after its norm, k_pe [T, rope] after its rotation)``."""
    kva = u @ w("w_kva")
    c_kv = _rms(kva[:, : d["kv_rank"]], w("kv_norm"), d["eps"])
    k_pe = ROTARY[rotary](kva[:, d["kv_rank"] :], positions, d["theta"])
    return c_kv, k_pe


def attention(d: Dict[str, Any], w, u, positions, rotary: str = "interleaved"):
    """``attn(u)`` over a whole sequence, ``u`` [T, H] normed, EXPANDED:
    K and V a head at every position."""
    NH, Dn, Dr, Dv = d["heads"], d["nope"], d["rope"], d["v_dim"]
    T = u.shape[0]
    c_q = _rms(u @ w("w_qa"), w("q_norm"), d["eps"])
    q = (c_q @ w("w_qb")).reshape(T, NH, Dn + Dr)
    q_nope = q[..., :Dn]
    q_pe = ROTARY[rotary](q[..., Dn:], positions, d["theta"])
    c_kv, k_pe = latents(d, w, u, positions, rotary)
    kv = (c_kv @ w("w_kvb")).reshape(T, NH, Dn + Dv)
    k_nope, v = kv[..., :Dn], kv[..., Dn:]
    outs = []
    for t0 in range(0, T, QUERY_BLOCK):
        t1 = min(t0 + QUERY_BLOCK, T)
        scores = (
            jnp.einsum("tnd,snd->nts", q_nope[t0:t1], k_nope[:t1])
            + jnp.einsum("tnd,sd->nts", q_pe[t0:t1], k_pe[:t1])
        ) / jnp.sqrt(F32(Dn + Dr))
        causal = positions[t0:t1, None] >= positions[None, :t1]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        outs.append(
            jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v[:t1])
        )
    return jnp.concatenate(outs).reshape(T, NH * Dv) @ w("wo")


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def route(d: Dict[str, Any], logits, bias):
    """``logits`` [T, E] float32, ``bias`` [E] -> (gates [T, E], zero
    off the chosen experts; gap [T] between the k-th and (k+1)-th of
    ``s + bias``, in its standard deviations, inf when every expert is
    chosen)."""
    E, K = d["experts"], d["top_k"]
    T = logits.shape[0]
    s = jax.nn.sigmoid(logits)
    chosen_by = s + bias
    top_e = jax.lax.top_k(chosen_by, K)[1]
    p = jnp.take_along_axis(s, top_e, axis=-1)
    p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20) * d["scale"]
    gates = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], top_e].set(p)
    if K == E:
        return gates, jnp.full((T,), jnp.inf, F32)
    ranked = jax.lax.top_k(chosen_by, K + 1)[0]
    gap = (ranked[:, K - 1] - ranked[:, K]) / jnp.std(chosen_by, axis=-1)
    return gates, gap


def routed_ffn(d: Dict[str, Any], moe: Dict[str, Any], index, u,
               shared: bool = True):
    """Routed layer ``index`` (among the routed ones) over normed ``u``
    [T, H]: (the held experts' weighted terms + the shared expert
    [T, H], gap [T]). Expert j of the stack is the router's expert
    ``first + j``."""
    w = layer_weight(moe, index)
    gates, gap = route(d, u @ w("router"), w("router_bias"))

    def add_expert(j, acc):
        def we(name):
            stack = moe[name]
            return jax.lax.dynamic_slice(
                stack, (index, j, 0, 0), (1, 1) + stack.shape[2:]
            )[0, 0].astype(F32)

        y = swiglu(u, we("we_gate"), we("we_up"), we("we_down"))
        g = jax.lax.dynamic_slice_in_dim(gates, d["first"] + j, 1, axis=1)
        return acc + g * y

    out = jax.lax.fori_loop(0, d["held"], add_expert, jnp.zeros_like(u))
    if shared:
        out = out + swiglu(
            u, w("shared_gate"), w("shared_up"), w("shared_down")
        )
    return out, gap


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7, 8))
def _layer_jit(frozen, routed, layers, index, f_index, h_pos, shared, rotary,
               part):
    """One layer over a whole sequence: (h [T, H], gap [T], inf for a
    layer that does not route). ``part``: "both", or "ffn" alone (the
    tests': a layer's FFN output on a given input)."""
    d = dict(frozen)
    h, positions = h_pos
    gap = jnp.full((h.shape[0],), jnp.inf, F32)
    if part != "ffn":
        w = layer_weight(layers["mla"], index)
        h = h + attention(
            d, w, _rms(h, w("attn_norm"), d["eps"]), positions, rotary
        )
    if routed:
        w = layer_weight(layers["moe"], f_index)
        y, gap = routed_ffn(
            d, layers["moe"], f_index, _rms(h, w("mlp_norm"), d["eps"]), shared
        )
    else:
        w = layer_weight(layers["dense"], f_index)
        y = swiglu(
            _rms(h, w("mlp_norm"), d["eps"]),
            w("w_gate"), w("w_up"), w("w_down"),
        )
    if part == "ffn":
        return y, gap
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
    rotary: str = "interleaved", return_hidden: bool = False,
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of routed layers whose selection there was a near tie. ``experts``,
    ``shared`` and ``rotary`` are the tests': a share of the stack's
    experts, the shared expert left out, the other rotary pairing.
    ``return_hidden`` gives the residual stream before the final norm
    in the logits' place."""
    d = dims_of(cfg)
    params, d = _held(params, experts, d)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for i in range(d["layers"]):
            routed = i >= d["dense_layers"]
            h, gap = _layer_jit(
                frozen, routed, params["layers"], i,
                i - d["dense_layers"] if routed else i, (h, positions),
                shared, rotary, "both",
            )
            ties = ties + (gap[at] < TIE_MARGIN)
        if return_hidden:
            return h[at], ties
        logits = _head(
            params["lm_head"], params["final_norm"], h[at], d["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions, **kw):
    return logits_and_near_ties(cfg, params, ids, score_positions, **kw)[0]


def ffn_of_layer(cfg, params, layer: int, u_in, *,
                 experts: Optional[Tuple[int, int]] = None,
                 shared: bool = True):
    """Layer ``layer``'s FFN output ``[T, H]`` on the residual stream
    ``u_in`` [T, H] (its own norm applied): the tests' view of ONE
    layer, for adding the shares up."""
    d = dims_of(cfg)
    params, d = _held(params, experts, d)
    routed = layer >= d["dense_layers"]
    with jax.default_matmul_precision("highest"):
        y, _ = _layer_jit(
            tuple(sorted(d.items())), routed, params["layers"], layer,
            layer - d["dense_layers"] if routed else layer,
            (jnp.asarray(u_in, F32),
             jnp.arange(len(u_in), dtype=jnp.int32)),
            shared, "interleaved", "ffn",
        )
    return y
