"""Nemotron-H decoder with routed blocks (``model_type`` ``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B), plain ``jax.numpy`` in float32.

Written from the published ``config.json`` keys, the family's published
description and the recurrent form of Mamba-2 (Dao and Gu, 2024),
independent of ``sutro_tpu/``: no kernels, no cache, no batching, no
chunked scan, no sort of rows by expert. ``h`` is the residual stream;
``hybrid_override_pattern`` gives one symbol a block and every block is
ONE sublayer under one norm:

    h = embed[ids]
    block i:   h = h + f_i(RMSNorm_i(h))        f_i by pattern[i]:
               "M" Mamba-2, "*" attention, "E" routed FFN
    logits = RMSNorm(h) lm_head                                (untied)

    "*":  GQA (num_attention_heads query heads over num_key_value_heads
          K/V heads of head_dim), no biases, no QK norm, NO rotary
          embedding; causal softmax of q k^T / sqrt(head_dim)

    "M":  I = mamba_num_heads * mamba_head_dim, N = ssm_state_size,
          G = n_groups, K = conv_kernel
          [z | xBC | dt] = u W_in       widths I, I + 2GN, heads
          xBC_t = silu(sum_{j<K} w_conv[:, j] * xBC_{t-(K-1)+j} + b_conv)
                                        xBC_s = 0 for s < 0
          [x | B | C] = xBC             widths I, GN, GN
          dt = softplus(dt + dt_bias) ;  A = -exp(a_log)        a head
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T            a head,
                S [head_dim, N], S_{-1} = 0, B and C its group's
          y_t = S_t C_t + D x_t
          f(u)_t = (RMSNorm_g(y_t * silu(z_t)) * w_norm) W_out
          RMSNorm_g: the mean of squares over ONE GROUP's I / G channels
          at a time (the gate before the norm)

    "E":  s = sigmoid(u W_router)                 float32, E_pub wide
          chosen = top-k of (s + e_score_correction_bias)
                   (n_group = topk_group = 1: no group limit)
          p = s[chosen] / (sum s[chosen] + 1e-20)   (norm_topk_prob)
          p = p * routed_scaling_factor
          expert_e(u) = (relu(u W_up_e))^2 W_down_e       two matrices
          f(u) = sum_{e chosen} p_e expert_e(u) + shared(u)
          shared(u) = (relu(u S_up))^2 S_down   width
                      moe_shared_expert_intermediate_size, every token

**The share.** The configuration's file may state a chip's share of a
deployment (``share``: ``experts_published``, ``first_expert``): the
router keeps ``experts_published`` outputs and its top-k, and the sum
over the chosen experts runs over those that are HELD here, experts
``first_expert .. first_expert + n_routed_experts`` (the file's
``n_routed_experts`` counts the held ones). What the absent experts
would add is left out, as the system leaves it out; the shared expert
is computed whole. ``logits_and_near_ties(..., experts=(first, count))``
takes another share of the same weights: the tests add the shares up. A
sliced vocabulary is a smaller vocabulary: the embedding and the head
have ``vocab_size`` rows and columns and nothing else is said of it.

Weights arrive in the layout the system serves them in, stacked per kind
of block: ``layers["mamba"]`` (``attn_norm``, ``w_in`` [L_m, H, 2I +
2GN], ``w_dt`` [L_m, H, heads], ``w_conv`` [L_m, I + 2GN, K],
``b_conv``, ``dt_bias``, ``a_log``, ``d_skip`` [L_m, heads],
``gate_norm`` [L_m, I], ``w_out`` [L_m, I, H]), ``layers["attn"]``
(``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``) and ``layers["moe"]``
(``mlp_norm``, ``router`` [L_e, H, E_pub], ``router_bias`` [L_e, E_pub],
``we_up_t`` [L_e, E_held, F, H] (each expert's first matrix
output-major: ``u W_up`` is ``u @ we_up_t[e].T``), ``we_down`` [L_e,
E_held, F, H],
``shared_up`` [L_e, H, Fs], ``shared_down`` [L_e, Fs, H]); block i's
weights are its kind's next in order.

Departures from the published description and code, each deliberate:
no rotary embedding in the attention blocks (the family's published
description; the file's ``rope_theta`` and ``partial_rotary_factor``
are the configuration class's defaults and nothing reads them); I is
``mamba_num_heads * mamba_head_dim`` = 4,096, not ``expand *
hidden_size`` = 5,376 (the published code sizes the projections by
heads x head_dim; ``expand`` is unused); the 1e-20 under the chosen
scores' sum is the published code's; the conv is the K-term sum above
(published: a ``Conv1d`` over a left-padded sequence: the same
numbers); ``w_conv`` is [C, K] (published [C, 1, K]); projections are
stored input-major; the mixer's input projection is two matrices
(``w_in`` the published one's columns for z and xBC, ``w_dt`` its last
``heads`` columns: the same numbers).

What it refuses rather than guesses: a pattern symbol other than ``M``,
``*``, ``E`` (``-``, a dense MLP block, is in the family and not in this
model); ``n_group`` or ``topk_group`` other than 1; ``norm_topk_prob``
false; ``mlp_hidden_act`` other than ``relu2``; ``mamba_hidden_act``
other than ``silu``; ``attention_bias``, ``mlp_bias``, ``use_bias`` or
``mamba_proj_bias`` true; ``use_conv_bias`` false; a tied head; more
than one shared expert.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .qwen3_dense import F32, _embed, _head, _rms, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
STACK = {"M": "mamba", "*": "attn", "E": "moe"}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    pattern = str(cfg["hybrid_override_pattern"])
    refuse = {
        f"pattern symbols {sorted(set(pattern) - set(STACK))}":
            bool(set(pattern) - set(STACK)),
        "a pattern of another length than num_hidden_layers":
            len(pattern) != int(cfg["num_hidden_layers"]),
        "n_group != 1": int(cfg.get("n_group", 1)) != 1,
        "topk_group != 1": int(cfg.get("topk_group", 1)) != 1,
        "norm_topk_prob false": cfg.get("norm_topk_prob") is not True,
        "mlp_hidden_act other than relu2": cfg.get("mlp_hidden_act") != "relu2",
        "mamba_hidden_act other than silu":
            cfg.get("mamba_hidden_act", "silu") != "silu",
        "a projection bias": any(
            bool(cfg.get(k)) for k in
            ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias")
        ),
        "use_conv_bias false": not cfg.get("use_conv_bias", True),
        "a tied head": bool(cfg.get("tie_word_embeddings", False)),
        "n_shared_experts != 1": int(cfg.get("n_shared_experts", 1)) != 1,
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference nemotron_h_moe does not implement: {', '.join(bad)}"
        )
    share = cfg.get("share") or {}
    held = int(cfg["n_routed_experts"])
    dims = {
        "pattern": pattern,
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "eps": float(cfg["norm_eps"]),
        "m_heads": int(cfg["mamba_num_heads"]),
        "m_head_dim": int(cfg["mamba_head_dim"]),
        "m_state": int(cfg["ssm_state_size"]),
        "m_groups": int(cfg["n_groups"]),
        "m_conv": int(cfg["conv_kernel"]),
        "experts": int(share.get("experts_published", held)),
        "first": int(share.get("first_expert", 0)),
        "held": held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
    }
    if not 1 <= dims["top_k"] <= dims["experts"]:
        raise ValueError("nemotron_h_moe: num_experts_per_tok outside 1..experts")
    if dims["first"] + dims["held"] > dims["experts"]:
        raise ValueError("nemotron_h_moe: the held experts are not among the router's")
    return dims


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def attention(d: Dict[str, Any], w, u, positions):
    """``f(u)`` of a "*" block over a whole sequence, ``u`` [T, H] normed."""
    NH, KVH, Dh = d["heads"], d["kv_heads"], d["head_dim"]
    T = u.shape[0]
    q = (u @ w("wq")).reshape(T, NH, Dh)
    k = jnp.repeat((u @ w("wk")).reshape(T, KVH, Dh), NH // KVH, axis=1)
    v = jnp.repeat((u @ w("wv")).reshape(T, KVH, Dh), NH // KVH, axis=1)
    scores = jnp.einsum("tnd,snd->nts", q, k) / jnp.sqrt(F32(Dh))
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, NH * Dh) @ w("wo")


def mamba(d: Dict[str, Any], w, u, norm_groups: Optional[int] = None):
    """``f(u)`` of an "M" block over a whole sequence from a zero state,
    ``u`` [T, H] normed: the recurrence one token at a time.
    ``norm_groups`` other than the configuration's computes ANOTHER
    model (the tests' use)."""
    Hm, P, N = d["m_heads"], d["m_head_dim"], d["m_state"]
    G, K = d["m_groups"], d["m_conv"]
    I, T = Hm * P, u.shape[0]
    zx = u @ w("w_in")
    z, xbc, dt = zx[:, :I], zx[:, I:], u @ w("w_dt")
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    taps = w("w_conv")                                        # [C, K]
    xbc = sum(padded[j : j + T] * taps[:, j] for j in range(K)) + w("b_conv")
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :I].reshape(T, Hm, P)
    per = Hm // G
    Bm = jnp.repeat(xbc[:, I : I + G * N].reshape(T, G, N), per, axis=1)
    Cm = jnp.repeat(xbc[:, I + G * N :].reshape(T, G, N), per, axis=1)
    dt = jax.nn.softplus(dt + w("dt_bias"))                   # [T, Hm]
    A = -jnp.exp(w("a_log"))                                  # [Hm]

    def token(S, t):
        x_t, B_t, C_t, dt_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            dt_t[:, None, None] * x_t[:, :, None] * B_t[:, None, :]
        )
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((Hm, P, N), F32), (x, Bm, Cm, dt))
    y = (y + w("d_skip")[:, None] * x).reshape(T, I) * jax.nn.silu(z)
    ng = G if norm_groups is None else norm_groups
    y = y.reshape(T, ng, I // ng)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + d["eps"])
    return (y.reshape(T, I) * w("gate_norm")) @ w("w_out")


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
    """Routed block ``index`` (among the routed ones) over normed ``u``
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

        y = relu2(u @ we("we_up_t").T) @ we("we_down")
        g = jax.lax.dynamic_slice_in_dim(gates, d["first"] + j, 1, axis=1)
        return acc + g * y

    out = jax.lax.fori_loop(0, d["held"], add_expert, jnp.zeros_like(u))
    if shared:
        out = out + relu2(u @ w("shared_up")) @ w("shared_down")
    return out, gap


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7))
def _block_jit(frozen, symbol, layers, index, h, positions, norm_groups,
               shared):
    """One block over a whole sequence: (h [T, H], gap [T], inf for a
    block that does not route)."""
    d = dict(frozen)
    w = layer_weight(layers[STACK[symbol]], index)
    no_gap = jnp.full((h.shape[0],), jnp.inf, F32)
    if symbol == "M":
        u = _rms(h, w("attn_norm"), d["eps"])
        return h + mamba(d, w, u, norm_groups), no_gap
    if symbol == "*":
        u = _rms(h, w("attn_norm"), d["eps"])
        return h + attention(d, w, u, positions), no_gap
    u = _rms(h, w("mlp_norm"), d["eps"])
    y, gap = routed_ffn(d, layers["moe"], index, u, shared)
    return h + y, gap


def _held(params, experts: Optional[Tuple[int, int]], d):
    """``params`` with the routed stacks cut to experts ``experts`` =
    (first, count) OF THE STACK HANDED IN (whose first is the file's
    ``first_expert``), and ``d`` saying so."""
    if experts is None:
        return params, d
    first, count = experts
    moe = dict(params["layers"]["moe"])
    for name in ("we_up_t", "we_down"):
        moe[name] = moe[name][:, first : first + count]
    layers = dict(params["layers"], moe=moe)
    return dict(params, layers=layers), dict(
        d, first=d["first"] + first, held=count
    )


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], *,
    experts: Optional[Tuple[int, int]] = None, shared: bool = True,
    norm_groups: Optional[int] = None, return_hidden: bool = False,
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of routed blocks whose selection there was a near tie. ``experts``,
    ``shared`` and ``norm_groups`` are the tests': a share of the
    stack's experts, the shared expert left out, another grouping of
    the gated norm. ``return_hidden`` gives the residual stream before
    the final norm in the logits' place."""
    d = dims_of(cfg)
    params, d = _held(params, experts, d)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    seen = {k: 0 for k in STACK}
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for symbol in d["pattern"]:
            h, gap = _block_jit(
                frozen, symbol, params["layers"], seen[symbol], h, positions,
                norm_groups, shared,
            )
            seen[symbol] += 1
            ties = ties + (gap[at] < TIE_MARGIN)
        if return_hidden:
            return h[at], ties
        logits = _head(
            params["lm_head"], params["final_norm"], h[at], d["eps"], False
        )
    return logits, ties


def logits_at(cfg, params, ids, score_positions, **kw):
    return logits_and_near_ties(cfg, params, ids, score_positions, **kw)[0]
