"""Solar Open 2 decoder (``model_type`` ``solar_open2``: Solar-Open2-250B),
plain ``jax.numpy`` in float32.

Written from the published ``config.json`` keys and the published
equations of Kimi Delta Attention (Kimi Linear, arXiv:2510.26692),
independent of ``sutro_tpu/``: no kernels, no cache, no batching, no
chunk form, no triangular solve, no sort of rows by expert. The delta
rule is the recurrence ONE TOKEN AT A TIME. ``h`` is the residual
stream; every layer is a mixer then a routed FFN, each under its own
RMSNorm:

    h = embed[ids]
    layer i:   h = h + mixer_i(RMSNorm(h)) ;  h = h + ffn_i(RMSNorm(h))
               mixer_i softmax GQA if i in gqa_layers, else KDA
    logits = RMSNorm(h) lm_head                                (untied)

    GQA:  num_attention_heads query heads over num_key_value_heads K/V
          heads of head_dim, no biases, no QK norm, NO rotary embedding
          (use_rope false); causal softmax of q k^T / sqrt(head_dim);
          then an OUTPUT GATE a channel from the layer's input
          (use_gqa_gate): f(u) = (concat_h o_h * sigmoid(u W_attn_gate)) W_o

    KDA:  H = linear_attn_config.num_heads heads of dk = dv =
          linear_attn_config.head_dim, I = H dk, K =
          short_conv_kernel_size
          [q | k | v]_t = silu(sum_{j<K} w_conv[:, j] * (u W_qkv)_{t-(K-1)+j})
                          (causal, depthwise, no bias; 0 before the start)
          q = l2norm(q) / sqrt(dk) ;  k = l2norm(k)        a head,
              l2norm(x) = x / sqrt(sum x^2 + 1e-6)
          beta = 2 sigmoid(u W_beta)                        a head, in (0, 2)
          g = -exp(a_log) * softplus(u W_fa W_fb + dt_bias) a CHANNEL of
              the key axis (a_log a head, dt_bias a channel); no bound
          S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
                S [dk (key), dv (value)] a head, S_{-1} = 0
          o_t = S_t^T q_t
          f(u)_t = (RMSNorm_dk(o_t) * w_norm * sigmoid(u W_ga W_gb + b_g)) W_out

    FFN:  s = sigmoid(u W_router)                 float32, E_pub wide
          chosen = top-k of (s + bias)
          p = s[chosen] / (sum s[chosen] + 1e-20)   (norm_topk_prob)
          p = p * routed_scaling_factor
          expert_e(u) = (silu(u W_gate_e) * (u W_up_e)) W_down_e
          f(u) = sum_{e chosen} p_e expert_e(u) + shared(u)   (one shared
          SwiGLU expert of moe_intermediate_size, unweighted)

**The share.** As ``nemotron_h_moe`` / ``mla_moe``: the file's ``share``
(``experts_published``, ``first_expert``) says which experts are HELD
here (the file's ``n_routed_experts`` counts them); the router keeps
``experts_published`` outputs and its top-k, the absent experts' terms
are left out as the system leaves them out, the shared expert is whole.
``logits_and_near_ties(..., experts=(first, count))`` takes another
share of the same weights: the tests add the shares up. A sliced
vocabulary is a smaller vocabulary.

Weights arrive in the layout the system serves them in, stacked per
kind: ``layers["attn"]`` (``attn_norm``, ``wq``, ``wk``, ``wv``,
``w_attn_gate`` [L_a, H, heads*head_dim], ``wo``), ``layers["kda"]``
(``attn_norm``, ``w_qkv`` [L_k, H, 3I] columns [q | k | v], ``w_conv``
[L_k, 3I, K], ``w_fa`` [L_k, H, R], ``w_fb`` [L_k, R, I], ``dt_bias``
[L_k, I], ``a_log`` [L_k, heads], ``w_beta`` [L_k, H, heads], ``w_ga``,
``w_gb``, ``b_g`` [L_k, I], ``o_norm`` [L_k, dk], ``w_out`` [L_k, I,
H]) and ``layers["moe"]`` (``mlp_norm``, ``router`` [L, H, E_pub],
``router_bias``, ``we_gate``, ``we_up`` [L, E_held, H, F], ``we_down``
[L, E_held, F, H], ``shared_gate``, ``shared_up``, ``shared_down``);
layer i's weights are its kind's next in order.

Inferences from the published file, none of which changes which
mechanism runs (the configuration's ``assumed`` has the same lines):
(1) ``kda_use_full_proj`` false = the paper's low-rank pairs for the
decay and the gate, rank = the head's width (the published "A15B"
agrees; full-rank projections would count 17.0 B a token); (2)
``kda_allow_neg_eigval`` true = ``beta`` doubled, so ``I - beta k k^T``
has the eigenvalue ``1 - beta`` in (-1, 1); (3) the decay is the
paper's, unbounded below (the file has no bound key); (4) ``b_g``, a
bias on the gate's second matrix, as the published layer's code has;
(5) ``use_gqa_gate`` gates the attention output a CHANNEL from the
layer's input; no QK norm (no key names one); (6) the router scores by
sigmoid with a selection bias as the family's earlier model does (the
file lacks ``scoring_func``; ``router_score`` in the file, where
present, must say ``sigmoid``); (7) ``a_log`` and ``dt_bias`` are
parameters like any other here: how seeded ones are drawn is the
system's (``assumed.weights``).

Departures in layout only: ``w_qkv`` is the three published projections
side by side and ``w_conv`` the three convolutions' taps stacked (the
same numbers); projections are stored input-major; ``w_conv`` is [C, K].

``variant`` (the tools' controls, each ANOTHER model that must NOT pass
as this one): ``"no_decay"`` sets ``g`` to 0, ``"no_delta"`` drops the
``- beta k k^T`` term (``S_t = Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``).

What it refuses rather than guesses: ``use_rope`` true; ``use_gqa_gate``
false; ``kda_use_full_proj`` true; ``kda_allow_neg_eigval`` false;
``first_k_dense_replace`` other than 0; ``norm_topk_prob`` false; a tied
head; more than one shared expert; ``linear_attn_config.num_kv_heads``
other than null; ``gqa_layers`` outside the depth; a ``router_score``
other than ``sigmoid``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .qwen3_dense import F32, _embed, _head, _rms, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
VARIANTS = (None, "no_decay", "no_delta")


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    lin = dict(cfg["linear_attn_config"])
    depth = int(cfg["num_hidden_layers"])
    gqa = tuple(int(i) for i in cfg["gqa_layers"])
    refuse = {
        "use_rope true": bool(cfg.get("use_rope", False)),
        "use_gqa_gate false": cfg.get("use_gqa_gate") is not True,
        "kda_use_full_proj true": bool(cfg.get("kda_use_full_proj", False)),
        "kda_allow_neg_eigval false":
            cfg.get("kda_allow_neg_eigval") is not True,
        "first_k_dense_replace != 0":
            int(cfg.get("first_k_dense_replace", 0)) != 0,
        "norm_topk_prob false": cfg.get("norm_topk_prob") is not True,
        "a tied head": bool(cfg.get("tie_word_embeddings", False)),
        "n_shared_experts != 1": int(cfg.get("n_shared_experts", 1)) != 1,
        "linear_attn_config.num_kv_heads": lin.get("num_kv_heads") is not None,
        "gqa_layers outside the depth": any(not 0 <= i < depth for i in gqa),
        "a router_score other than sigmoid":
            cfg.get("router_score", "sigmoid") != "sigmoid",
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference kda_gqa_moe does not implement: {', '.join(bad)}"
        )
    share = cfg.get("share") or {}
    held = int(cfg["n_routed_experts"])
    dims = {
        "depth": depth,
        "gqa": gqa,
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "eps": float(cfg["rms_norm_eps"]),
        "k_heads": int(lin["num_heads"]),
        "k_head_dim": int(lin["head_dim"]),
        "k_conv": int(lin["short_conv_kernel_size"]),
        "experts": int(share.get("experts_published", held)),
        "first": int(share.get("first_expert", 0)),
        "held": held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
    }
    if not 1 <= dims["top_k"] <= dims["experts"]:
        raise ValueError("kda_gqa_moe: num_experts_per_tok outside 1..experts")
    if dims["first"] + dims["held"] > dims["experts"]:
        raise ValueError("kda_gqa_moe: the held experts are not among the router's")
    return dims


def attention(d: Dict[str, Any], w, u):
    """``f(u)`` of a GQA layer over a whole sequence, ``u`` [T, H] normed:
    no rotary embedding, then the output gate a channel."""
    NH, KVH, Dh = d["heads"], d["kv_heads"], d["head_dim"]
    T = u.shape[0]
    q = (u @ w("wq")).reshape(T, NH, Dh)
    k = jnp.repeat((u @ w("wk")).reshape(T, KVH, Dh), NH // KVH, axis=1)
    v = jnp.repeat((u @ w("wv")).reshape(T, KVH, Dh), NH // KVH, axis=1)
    scores = jnp.einsum("tnd,snd->nts", q, k) / jnp.sqrt(F32(Dh))
    at = jnp.arange(T)
    scores = jnp.where((at[:, None] >= at[None, :])[None], scores, -jnp.inf)
    attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    gate = jax.nn.sigmoid(u @ w("w_attn_gate"))
    return (attn.reshape(T, NH * Dh) * gate) @ w("wo")


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_inputs(d: Dict[str, Any], w, u, variant: Optional[str] = None):
    """A KDA layer's per-token ``(q, k, v [T, H, dk], beta [T, H],
    g [T, H, dk])`` from normed ``u`` [T, hidden], the sequence from a
    zero conv state."""
    Hk, dk, K = d["k_heads"], d["k_head_dim"], d["k_conv"]
    I, T = Hk * dk, u.shape[0]
    qkv = u @ w("w_qkv")
    padded = jnp.concatenate([jnp.zeros((K - 1, 3 * I), F32), qkv])
    taps = w("w_conv")                                        # [3I, K]
    qkv = jax.nn.silu(sum(padded[j : j + T] * taps[:, j] for j in range(K)))
    q = l2norm(qkv[:, :I].reshape(T, Hk, dk)) / jnp.sqrt(F32(dk))
    k = l2norm(qkv[:, I : 2 * I].reshape(T, Hk, dk))
    v = qkv[:, 2 * I :].reshape(T, Hk, dk)
    beta = 2.0 * jax.nn.sigmoid(u @ w("w_beta"))              # [T, Hk]
    g = jax.nn.softplus((u @ w("w_fa")) @ w("w_fb") + w("dt_bias"))
    g = -jnp.exp(w("a_log"))[None, :, None] * g.reshape(T, Hk, dk)
    if variant == "no_decay":
        g = jnp.zeros_like(g)
    return q, k, v, beta, g


def kda_scan(q, k, v, beta, g, S0=None, variant: Optional[str] = None):
    """The recurrence one token at a time: ``(o [T, H, dv], S_T, every
    S_t [T, H, dk, dv])`` from ``S0`` (zeros)."""
    T, Hk, dk = q.shape
    if S0 is None:
        S0 = jnp.zeros((Hk, dk, v.shape[-1]), F32)

    def token(S, t):
        q_t, k_t, v_t, b_t, g_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        if variant != "no_delta":
            # (I - beta k k^T) S = S - beta k (k^T S)
            S = S - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum(
                "hk,hkv->hv", k_t, S
            )[:, None, :]
        S = S + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S, (jnp.einsum("hkv,hk->hv", S, q_t), S)

    S, (o, every) = jax.lax.scan(token, S0, (q, k, v, beta, g))
    return o, S, every


def kda(d: Dict[str, Any], w, u, variant: Optional[str] = None):
    """``f(u)`` of a KDA layer over a whole sequence from a zero state."""
    T = u.shape[0]
    q, k, v, beta, g = kda_inputs(d, w, u, variant)
    o, _, _ = kda_scan(q, k, v, beta, g, variant=variant)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + d["eps"])
    o = o * w("o_norm")
    gate = jax.nn.sigmoid((u @ w("w_ga")) @ w("w_gb") + w("b_g"))
    return (o.reshape(T, -1) * gate) @ w("w_out")


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


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def routed_ffn(d: Dict[str, Any], moe: Dict[str, Any], index, u,
               shared: bool = True):
    """Routed layer ``index`` over normed ``u`` [T, H]: (the held
    experts' weighted terms + the shared expert [T, H], gap [T]). Expert
    j of the stack is the router's expert ``first + j``."""
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
def _layer_jit(frozen, kind, layers, m_index, f_index, h, shared, variant,
               part):
    """One layer over a whole sequence: (h [T, H], gap [T]). ``part``:
    "both", or "ffn" alone (the tests': a layer's FFN output added to a
    given input)."""
    d = dict(frozen)
    if part != "ffn":
        w = layer_weight(layers[kind], m_index)
        u = _rms(h, w("attn_norm"), d["eps"])
        h = h + (attention(d, w, u) if kind == "attn" else kda(d, w, u, variant))
    w = layer_weight(layers["moe"], f_index)
    u = _rms(h, w("mlp_norm"), d["eps"])
    y, gap = routed_ffn(d, layers["moe"], f_index, u, shared)
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
    """Full causal forward of ``ids`` ([T] ints), a layer at a time (so
    that 16 sequences of 200 positions at the published widths fit one
    chip beside the system): float32 logits ``[len(score_positions), V]``
    and, per scored position, the number of routed layers whose
    selection there was a near tie. ``experts``, ``shared``, ``part`` are
    the tests' (a share of the stack's experts, the shared expert left
    out, the FFNs alone); ``variant`` the controls' (module docstring).
    ``return_hidden`` gives the residual stream before the final norm in
    the logits' place."""
    if variant not in VARIANTS:
        raise ValueError(f"kda_gqa_moe: variant {variant!r} not in {VARIANTS}")
    d = dims_of(cfg)
    params, d = _held(params, experts, d)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    seen = {"attn": 0, "kda": 0}
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for i in range(d["depth"]):
            kind = "attn" if i in d["gqa"] else "kda"
            h, gap = _layer_jit(
                frozen, kind, params["layers"], seen[kind], i, h, shared,
                variant, part,
            )
            seen[kind] += 1
            ties = ties + (gap[at] < TIE_MARGIN)
        if return_hidden:
            return h[at], ties
        logits = _head(
            params["lm_head"], params["final_norm"], h[at], d["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions, **kw):
    return logits_and_near_ties(cfg, params, ids, score_positions, **kw)[0]
