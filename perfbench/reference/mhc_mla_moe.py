"""Latent-attention decoder with routed layers whose residual stream is
FOUR LANES mixed by manifold-constrained hyper-connections (``model_type``
``xing4_0``: Xing4.0-29B-A4B), plain ``jax.numpy`` in float32.

Written from the published ``config.json`` keys and from the published
descriptions (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606; DeepSeek-V2 "Multi-head Latent Attention"; DeepSeek-V3's
router and its YaRN), independent of ``sutro_tpu/``: no kernels, no cache,
no batching, no absorbed products, no token-minor coefficient arrays, no
sum of slabs. A token's residual is ``X`` ``[n, C]``, ``n = hc_mult``:

    X_0[i] = embed[id]                        every lane the same
    layer l:  X = hc(attn_l, X) ;  X = hc(ffn_l, X)
    logits = RMSNorm(sum_i X_L[i]) lm_head                    (untied)

    hc(F, X), ONE token; the sublayer F owns phi [n C, n^2 + 2 n] (held
    output-major, [n^2 + 2 n, n C]), b [n^2 + 2 n], alpha = (pre, post,
    res):
        x = vec(X) ;  r = 1 / sqrt(mean(x^2) + rms_norm_eps)
        m = r * (x phi)                                        [n^2 + 2 n]
        H_pre  = sigmoid(alpha_pre m[0:n] + b[0:n])            in (0, 1)^n
        H_post = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])       in (0, 2)^n
        M = exp(clip(alpha_res mat(m[2n:]) + mat(b[2n:]),
                     mhc_h_res_clamp_min, mhc_h_res_clamp_max))    [n, n]
        hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps)
                                  M <- M / (rowsum(M) + hc_eps)
        H_res = M            (doubly stochastic to rounding; mat is row-major)
        u = sum_j H_pre[j] X[j] ;  y = F(u)        F norms u itself
        X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

    attn (every layer): ``mla_moe``'s latent attention, EXPANDED, with
    YaRN on the rotary part (``rope_scaling`` type yarn):
        inv_freq = by-parts: theta^(-2i/rope) for the pairs that turn more
            than beta_fast times in original_max_position_embeddings,
            that over factor for those that turn fewer than beta_slow
            times, a linear ramp in the pair's index between
        cos, sin times m(factor, mscale) / m(factor, mscale_all_dim),
            m(s, a) = 0.1 a ln s + 1
        score scale = m(factor, mscale_all_dim)^2 / sqrt(nope + rope)
    ffn: ``mla_moe``'s dense SwiGLU (layers < first_k_dense_replace) and
    routed layer (sigmoid scores, selection bias, top-k, the chosen
    scores over their sum (+1e-20) times routed_scaling_factor, every
    expert held, ONE shared expert).

``swiglu``, ``route`` and ``routed_ffn`` are ``mla_moe``'s, which take
every value from the dict handed to them; nothing of that file is edited.

Weights arrive in the layout the system serves them in
(``mla_moe``'s, stacked per kind), with each sublayer's hyper-connection
on its kind's stack: ``hc_mix_phi`` [L, n^2 + 2 n, n C], ``hc_mix_b``
[L, n^2 + 2 n], ``hc_mix_alpha`` [L, 3] on ``layers["mla"]``, ``hc_ffn_*``
likewise on ``layers["dense"]`` and ``layers["moe"]``.

``controls`` (a tuple of names) computes ANOTHER model, for the checks
that must FAIL: ``"res_identity"`` (H_res = I), ``"static"`` (alpha = 0:
the dynamic half of every coefficient off), ``"one_pass"`` (one Sinkhorn
pass in place of ``hc_sinkhorn_iters``), ``"plain_rope"`` (the plain
rotary frequencies and scale in place of YaRN's).

Departures and inferences: ``mhc_mla_moe.md``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from .mla_moe import routed_ffn, swiglu
from .qwen3_dense import F32, _embed, _head, _rms, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
QUERY_BLOCK = 512
CONTROLS = ("res_identity", "static", "one_pass", "plain_rope")


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    scaling = cfg.get("rope_scaling") or {}
    refuse = {
        "rope_scaling other than yarn": scaling.get("type") != "yarn",
        "rope_interleave false": cfg.get("rope_interleave", True) is not True,
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
        "hc_mult < 2": int(cfg.get("hc_mult", 1)) < 2,
        "a share of the experts": "share" in cfg,
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference mhc_mla_moe does not implement: {', '.join(bad)}"
        )
    experts = int(cfg["n_routed_experts"])
    return {
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
        "experts": experts, "first": 0, "held": experts,
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "lanes": int(cfg["hc_mult"]),
        "sinkhorn": int(cfg["hc_sinkhorn_iters"]),
        "hc_eps": float(cfg["hc_eps"]),
        "clamp": (float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])),
        "yarn_factor": float(scaling["factor"]),
        "yarn_original": int(scaling["original_max_position_embeddings"]),
        "yarn_beta": (float(scaling["beta_fast"]), float(scaling["beta_slow"])),
        "yarn_mscale": (float(scaling["mscale"]),
                        float(scaling["mscale_all_dim"])),
    }


def yarn_mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(d: Dict[str, Any]):
    """The by-parts inverse frequencies of the ``rope``-wide rotary part,
    [rope / 2], as the family's published code computes them."""
    dim, base = d["rope"], d["theta"]
    pair = jnp.arange(0, dim, 2, dtype=F32) / dim
    extrapolated = 1.0 / base ** pair
    interpolated = 1.0 / (d["yarn_factor"] * base ** pair)

    def pair_that_turns(times: float) -> float:
        return dim * math.log(
            d["yarn_original"] / (times * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(d["yarn_beta"][0])), 0)
    high = min(math.ceil(pair_that_turns(d["yarn_beta"][1])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0, 1)
    return interpolated * ramp + extrapolated * (1 - ramp)


def rotary(d: Dict[str, Any], x, positions, plain: bool):
    """x [T, ..., rope]: the pair (2i, 2i+1) turns by pos * inv_freq[i];
    cos and sin times YaRN's factor. ``plain``: the unscaled frequencies
    (the control's)."""
    D = x.shape[-1]
    if plain:
        inv, mult = d["theta"] ** (-jnp.arange(0, D, 2, dtype=F32) / D), 1.0
    else:
        inv = yarn_inv_freq(d)
        mult = yarn_mscale(d["yarn_factor"], d["yarn_mscale"][0]) / yarn_mscale(
            d["yarn_factor"], d["yarn_mscale"][1]
        )
    ang = positions.astype(F32)[:, None] * inv[None, :]       # [T, D/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (D // 2,)
    cos = jnp.cos(ang).reshape(shape) * mult
    sin = jnp.sin(ang).reshape(shape) * mult
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def attention(d: Dict[str, Any], w, u, positions, plain: bool):
    """``attn(u)`` over a whole sequence, ``u`` [T, H] normed, EXPANDED:
    K and V a head at every position."""
    NH, Dn, Dr, Dv = d["heads"], d["nope"], d["rope"], d["v_dim"]
    T = u.shape[0]
    c_q = _rms(u @ w("w_qa"), w("q_norm"), d["eps"])
    q = (c_q @ w("w_qb")).reshape(T, NH, Dn + Dr)
    q_nope, q_pe = q[..., :Dn], rotary(d, q[..., Dn:], positions, plain)
    kva = u @ w("w_kva")
    c_kv = _rms(kva[:, : d["kv_rank"]], w("kv_norm"), d["eps"])
    k_pe = rotary(d, kva[:, d["kv_rank"] :], positions, plain)
    kv = (c_kv @ w("w_kvb")).reshape(T, NH, Dn + Dv)
    k_nope, v = kv[..., :Dn], kv[..., Dn:]
    scale = 1.0 / math.sqrt(Dn + Dr)
    if not plain:
        scale *= yarn_mscale(d["yarn_factor"], d["yarn_mscale"][1]) ** 2
    outs = []
    for t0 in range(0, T, QUERY_BLOCK):
        t1 = min(t0 + QUERY_BLOCK, T)
        scores = (
            jnp.einsum("tnd,snd->nts", q_nope[t0:t1], k_nope[:t1])
            + jnp.einsum("tnd,sd->nts", q_pe[t0:t1], k_pe[:t1])
        ) * scale
        causal = positions[t0:t1, None] >= positions[None, :t1]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        outs.append(
            jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v[:t1])
        )
    return jnp.concatenate(outs).reshape(T, NH * Dv) @ w("wo")


def coefficients(d: Dict[str, Any], phi_t, b, alpha, X, controls=()):
    """ONE token's ``(H_pre [n], H_post [n], H_res [n, n])`` from its
    residual ``X`` [n, C]."""
    n = X.shape[0]
    x = X.reshape(-1)
    m = (phi_t @ x) / jnp.sqrt(jnp.mean(x * x) + d["eps"])
    if "static" in controls:
        alpha = jnp.zeros_like(alpha)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n : 2 * n] + b[n : 2 * n])
    lo, hi = d["clamp"]
    M = jnp.exp(jnp.clip(
        alpha[2] * m[2 * n :].reshape(n, n) + b[2 * n :].reshape(n, n), lo, hi
    ))
    for _ in range(1 if "one_pass" in controls else d["sinkhorn"]):
        M = M / (jnp.sum(M, axis=0, keepdims=True) + d["hc_eps"])   # columns
        M = M / (jnp.sum(M, axis=1, keepdims=True) + d["hc_eps"])   # rows
    if "res_identity" in controls:
        M = jnp.eye(n, dtype=F32)
    return h_pre, h_post, M


def hyper_connection(d: Dict[str, Any], w, prefix: str, X, f, controls=()):
    """``hc(F, X)`` over a sequence: X [T, n, C]; ``f`` maps u [T, C] to
    ``(y [T, C], whatever else it reports)``."""
    h_pre, h_post, h_res = jax.vmap(
        lambda Xt: coefficients(
            d, w(prefix + "phi"), w(prefix + "b"), w(prefix + "alpha"), Xt,
            controls,
        )
    )(X)
    u = jnp.einsum("tj,tjc->tc", h_pre, X)
    y, extra = f(u)
    return (
        jnp.einsum("tij,tjc->tic", h_res, X) + h_post[:, :, None] * y[:, None]
    ), extra


@functools.partial(jax.jit, static_argnums=(0, 1, 6))
def _layer_jit(frozen, routed, layers, index, f_index, x_pos, controls):
    """One layer over a whole sequence: (X [T, n, C], gap [T], inf for a
    layer that does not route)."""
    d = dict(frozen)
    X, positions = x_pos
    plain = "plain_rope" in controls
    w = layer_weight(layers["mla"], index)
    X, _ = hyper_connection(
        d, w, "hc_mix_", X,
        lambda u: (attention(
            d, w, _rms(u, w("attn_norm"), d["eps"]), positions, plain
        ), None),
        controls,
    )
    if routed:
        w = layer_weight(layers["moe"], f_index)

        def ffn(u):
            return routed_ffn(
                d, layers["moe"], f_index, _rms(u, w("mlp_norm"), d["eps"])
            )
    else:
        w = layer_weight(layers["dense"], f_index)

        def ffn(u):
            return swiglu(
                _rms(u, w("mlp_norm"), d["eps"]),
                w("w_gate"), w("w_up"), w("w_down"),
            ), jnp.full((u.shape[0],), jnp.inf, F32)

    return hyper_connection(d, w, "hc_ffn_", X, ffn, controls)


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], *, controls: Tuple[str, ...] = (),
    return_hidden: bool = False,
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of routed layers whose selection there was a near tie. ``controls``:
    the module's (ANOTHER model). ``return_hidden`` gives the summed
    stream before the final norm in the logits' place."""
    unknown = set(controls) - set(CONTROLS)
    if unknown:
        raise ValueError(f"mhc_mla_moe: unknown controls {sorted(unknown)}")
    d = dims_of(cfg)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        X = jnp.repeat(h[:, None, :], d["lanes"], axis=1)       # [T, n, C]
        for i in range(d["layers"]):
            routed = i >= d["dense_layers"]
            X, gap = _layer_jit(
                frozen, routed, params["layers"], i,
                i - d["dense_layers"] if routed else i, (X, positions),
                tuple(controls),
            )
            ties = ties + (gap[at] < TIE_MARGIN)
        h = jnp.sum(X[at], axis=1)
        if return_hidden:
            return h, ties
        logits = _head(
            params["lm_head"], params["final_norm"], h, d["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions, **kw):
    return logits_and_near_ties(cfg, params, ids, score_positions, **kw)[0]
