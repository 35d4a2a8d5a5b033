"""Granite 4.0-H decoder (``model_type`` ``granitemoehybrid``, dense
members: ``num_local_experts`` 0), plain ``jax.numpy`` in float32.

Written from the published ``config.json`` keys and the description of
Mamba-2 (Dao and Gu, 2024, the recurrent form), independent of
``sutro_tpu/``: no kernels, no cache, no batching, no chunked scan. The
recurrence is a token-by-token ``lax.scan`` over the whole sequence; the
program computes it in chunks, so the two share no algorithm. ``h`` is
the residual stream, ``r`` = ``residual_multiplier``:

    h = embed[ids] * embedding_multiplier
    layer i:   h = h + r * Mixer_i(RMSNorm(h))     Mixer_i by layer_types[i]
               h = h + r * FFN(RMSNorm(h))
    logits = (RMSNorm(h) embed^T) / logits_scaling            (tied head)

    FFN(u) = (silu(u W_gate) * (u W_up)) W_down               (no biases)

    "attention": GQA, no biases, no QK-norm; no rotary embedding when
               ``position_embedding_type`` is "nope" (rotate-half RoPE
               at ``rope_theta`` otherwise); causal softmax of
               q k^T * attention_multiplier

    "mamba":   I = mamba_n_heads * mamba_d_head, N = mamba_d_state,
               G = mamba_n_groups, K = mamba_d_conv
               [z | xBC | dt] = u W_in       widths I, I + 2GN, heads
               xBC_t = silu(sum_{j<K} w_conv[:, j] * xBC_{t-(K-1)+j} + b_conv)
                                             xBC_s = 0 for s < 0
               [x | B | C] = xBC             widths I, GN, GN
               dt = softplus(dt + dt_bias) ;  A = -exp(a_log)      a head
               S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          a head,
                     S [d_head, N], S_{-1} = 0, B and C its group's
               y_t = S_t C_t + D x_t
               Mixer(u)_t = (RMSNorm(y_t * silu(z_t)) * w_norm) W_out
               the norm over all I channels (one group)

Weights arrive in the layout the system serves them in, stacked per kind
of layer: ``layers["attn"]`` (``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo``; [L_attn, ...]), ``layers["mamba"]`` (``attn_norm``, ``w_in``
[L_m, H, 2I + 2GN], ``w_dt`` [L_m, H, heads], ``w_conv`` [L_m, I + 2GN, K], ``b_conv``,
``dt_bias``, ``a_log``, ``d_skip`` [L_m, heads], ``gate_norm`` [L_m, I],
``w_out`` [L_m, I, H]) and ``layers["dense"]`` (``mlp_norm``,
``w_gate``, ``w_up``, ``w_down``; one a layer); layer i's weights are
its kind's next in order.

Departures from the published code, each deliberate: the conv is the
K-term sum above (the published one a ``Conv1d`` over a left-padded
sequence: the same numbers); ``w_conv`` is [C, K] (published [C, 1, K]);
projections are stored input-major; the mixer's input projection is two
matrices (``w_in`` the published one's columns for z and xBC, ``w_dt``
its last ``heads`` columns: the same numbers); the FFN's input projection is two
matrices (published: one of twice the width, split in halves: the same
numbers); ``head_dim`` is ``hidden_size / num_attention_heads``.

What it refuses rather than guesses: ``num_local_experts`` > 0 (the
routed part is not written here), a ``layer_types`` entry other than
``mamba`` and ``attention``, ``mamba_proj_bias`` or ``attention_bias``
true, ``mamba_conv_bias`` false, an untied head, a
``normalization_function`` other than ``rmsnorm``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from .qwen3_dense import F32, _embed, _head, _rms, _rope, layer_weight


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    refuse = {
        "num_local_experts > 0": int(cfg.get("num_local_experts") or 0) > 0,
        "mamba_proj_bias": bool(cfg.get("mamba_proj_bias")),
        "attention_bias": bool(cfg.get("attention_bias")),
        "mamba_conv_bias false": not cfg.get("mamba_conv_bias", True),
        "an untied head": not cfg.get("tie_word_embeddings", True),
        "a norm other than rmsnorm":
            cfg.get("normalization_function", "rmsnorm") != "rmsnorm",
    }
    bad = [k for k, v in refuse.items() if v]
    kinds = tuple(cfg["layer_types"])
    if set(kinds) - {"mamba", "attention"}:
        bad.append(f"layer_types {sorted(set(kinds))}")
    if len(kinds) != int(cfg["num_hidden_layers"]):
        bad.append("layer_types of another length than num_hidden_layers")
    if bad:
        raise NotImplementedError(
            f"reference granite_hybrid does not implement: {', '.join(bad)}"
        )
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    heads, d_head = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if heads * d_head != int(cfg["mamba_expand"]) * H:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * hidden_size")
    return {
        "kinds": kinds,
        "heads": NH,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim") or H // NH),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg.get("rope_theta", 1e4)),
        "rope": cfg.get("position_embedding_type", "nope") != "nope",
        "attn_scale": float(cfg["attention_multiplier"]),
        "embed_mult": float(cfg["embedding_multiplier"]),
        "resid_mult": float(cfg["residual_multiplier"]),
        "logits_div": float(cfg["logits_scaling"]),
        "m_heads": heads, "m_head_dim": d_head,
        "m_state": int(cfg["mamba_d_state"]),
        "m_groups": int(cfg["mamba_n_groups"]),
        "m_conv": int(cfg["mamba_d_conv"]),
    }


def attention(d: Dict[str, Any], w, u, positions):
    """``Attn(u)`` over a whole sequence, ``u`` [T, H] normed."""
    NH, KVH, Dh = d["heads"], d["kv_heads"], d["head_dim"]
    T = u.shape[0]
    q = (u @ w("wq")).reshape(T, NH, Dh)
    k = (u @ w("wk")).reshape(T, KVH, Dh)
    v = (u @ w("wv")).reshape(T, KVH, Dh)
    if d["rope"]:
        q, k = _rope(q, positions, d["theta"]), _rope(k, positions, d["theta"])
    k = jnp.repeat(k, NH // KVH, axis=1)
    v = jnp.repeat(v, NH // KVH, axis=1)
    scores = jnp.einsum("tnd,snd->nts", q, k) * d["attn_scale"]
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, NH * Dh) @ w("wo")


def mamba(d: Dict[str, Any], w, u, gate_after_norm: bool = False):
    """``Mixer(u)`` over a whole sequence from a zero state, ``u``
    [T, H] normed: the recurrence one token at a time."""
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
    y = (y + w("d_skip")[:, None] * x).reshape(T, I)
    gate = jax.nn.silu(z)
    if gate_after_norm:
        y = _rms(y, w("gate_norm"), d["eps"]) * gate
    else:
        y = _rms(y * gate, w("gate_norm"), d["eps"])
    return y @ w("w_out")


def layer(d, layers, kind: str, m_index, f_index, h, positions,
          gate_after_norm: bool = False):
    stack = "attn" if kind == "attention" else "mamba"
    w = layer_weight(layers[stack], m_index)
    u = _rms(h, w("attn_norm"), d["eps"])
    if kind == "attention":
        mixed = attention(d, w, u, positions)
    else:
        mixed = mamba(d, w, u, gate_after_norm)
    h = h + d["resid_mult"] * mixed
    f = layer_weight(layers["dense"], f_index)
    u = _rms(h, f("mlp_norm"), d["eps"])
    ffn = (jax.nn.silu(u @ f("w_gate")) * (u @ f("w_up"))) @ f("w_down")
    return h + d["resid_mult"] * ffn


@functools.partial(jax.jit, static_argnums=(0, 2, 7))
def _layer_jit(frozen, layers, kind, m_index, f_index, h, positions,
               gate_after_norm):
    return layer(dict(frozen), layers, kind, m_index, f_index, h, positions,
                 gate_after_norm)


def logits_at(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], *, gate_after_norm: bool = False,
):
    """Full causal forward of ``ids`` ([T] ints); float32 logits
    ``[len(score_positions), V]`` at those positions.
    ``gate_after_norm`` computes ANOTHER model (the gate applied after
    the mixer's norm): the tests use it to show that the check has
    teeth."""
    d = dims_of(cfg)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    seen = {"attention": 0, "mamba": 0}
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids) * d["embed_mult"]
        for index, kind in enumerate(d["kinds"]):
            h = _layer_jit(
                frozen, params["layers"], kind, seen[kind], index, h,
                positions, gate_after_norm,
            )
            seen[kind] += 1
        logits = _head(params["embed"], params["final_norm"], h[at], d["eps"], True)
        return logits / d["logits_div"]
