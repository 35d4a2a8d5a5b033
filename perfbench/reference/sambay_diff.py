"""Phi-4-mini-flash-reasoning's decoder (``model_type`` ``phi4flash``:
SambaY, a decoder-hybrid-decoder, arXiv:2507.06607, with differential
attention, arXiv:2410.05258), plain ``jax.numpy`` in float32.

Written from the published ``config.json`` keys and the two papers'
equations (``sambay_diff.md`` lists every size the file has no key for),
independent of ``sutro_tpu/``: no kernels, no cache, no batching, no
chunked scan, no pairing of heads into wider ones. The recurrence is a
token-by-token ``lax.scan`` over the whole sequence, the differential
attention two masked softmaxes a head. ``h`` is the residual stream:

    h = embed[ids]
    layer l:   h = h + Mixer_l(LN(h))            LN: LayerNorm, scale and bias
               h = h + (up * silu(gate)) W_down  [gate | up] = LN(h) [W_gate | W_up]
    logits = LN(h) embed^T                       (tied head, no bias)

    Mixer_l, L layers, half = L / 2, every ``mb_per_layer``-th is "even":
      l even, l <= half      Mamba-1; layer ``half`` also hands on m = y
      l odd,  l <  half      differential attention, the last W positions
      l = half + 1           differential attention, every position; its
                             k and v are what the cross layers read
      l even, l >  half + 1  gated memory unit: (m * silu(u W_1)) W_2
      l odd,  l >  half + 1  differential CROSS attention: a query of its
                             own over layer half + 1's k and v

    Mamba-1:  I = 2 hidden, N = d_state, K = d_conv, R = dt_rank
      [x | z] = u W_in ;  x_t = silu(sum_j w_conv[:, j] x_{t-(K-1)+j} + b_conv)
      [r | B | C] = x W_x ;  dt = softplus(r W_dt + dt_bias)      [T, I]
      A = -exp(a_log)                                             [N, I]
      S_t = exp(dt_t A) * S_{t-1} + B_t (dt_t x_t)^T   S [N, I], S_{-1} = 0
      y_t = C_t S_t + D * x_t ;  out = (y * silu(z)) W_out ;  m = y

    Differential attention, heads of Dh, NH query and KVH key/value heads:
      q = u W_q + b_q ; k = u W_k + b_k ; v = u W_v + b_v (a cross layer: q alone)
      differential head i of NH / 2: q1 = q[2i], q2 = q[2i + 1];
      KV pair j = i // (NH / KVH): k1 = k[2j], k2 = k[2j + 1], v = [v[2j] | v[2j + 1]]
      a_c = softmax(q_c k_c^T / sqrt(Dh)) v     causal (and t - s < W in a window layer)
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
      lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
      o_i = RMSNorm(a1 - lambda a2; w) (1 - lambda_init(l))        [2 Dh]
      out = [o_0 | o_1 | ..] W_o + b_o

Weights arrive in the layout the system serves them in, stacked per kind
of layer: ``layers["mamba1"]`` (``w_in`` [L, H, 2I], ``w_conv`` [L, I, K],
``b_conv``, ``w_x`` [L, I, R + 2N], ``w_dt`` [L, R, I], ``dt_bias``,
``a_log`` [L, N, I], ``d_skip`` [L, I], ``w_out``), ``layers["swa"]`` and
``layers["attn"]`` (``wq``, ``wk``, ``wv``, ``wo``, ``bq``, ``bk``, ``bv``,
``bo``, ``lambda_q1/k1/q2/k2`` [L, Dh], ``diff_norm`` [L, 2 Dh]),
``layers["cross"]`` (the same without ``wk``, ``wv``, ``bk``, ``bv``),
``layers["gmu"]`` (``w_in`` [L, H, I], ``w_out``) and ``layers["dense"]``
(``w_gate``, ``w_up``, ``w_down``; one a layer); every stack carries its
norm's scale and bias (``attn_norm``, ``attn_norm_b``; ``mlp_norm``,
``mlp_norm_b``); layer l's weights are its kind's next in order.

``variant`` computes ANOTHER model, so that the checks have teeth:
``cross_reads_window`` (the cross layers see layer half + 1's last W
positions alone), ``no_lambda`` (lambda 0: the second softmax left out)
and ``memory_after_gate`` (the memory units take ``y * silu(z)``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .qwen3_dense import F32, _embed, layer_weight

VARIANTS = ("cross_reads_window", "no_lambda", "memory_after_gate")


def layout(layers: int, mb_per_layer: int) -> Sequence[str]:
    half = layers // 2
    kinds = []
    for l in range(layers):
        even = l % mb_per_layer == 0
        if l <= half:
            kinds.append("mamba1" if even else "swa")
        elif l == half + 1:
            kinds.append("attn")
        else:
            kinds.append("gmu" if even else "cross")
    return tuple(kinds)


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    refuse = {
        "mlp_bias": bool(cfg.get("mlp_bias")),
        "lm_head_bias": bool(cfg.get("lm_head_bias")),
        "an untied head": not cfg.get("tie_word_embeddings", True),
        "an activation other than silu": cfg.get("hidden_act", "silu") != "silu",
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference sambay_diff does not implement: {', '.join(bad)}"
        )
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    KVH = int(cfg["num_key_value_heads"])
    if NH % 2 or KVH % 2 or (NH // 2) % (KVH // 2):
        raise ValueError("differential heads pair an even number of heads")
    layers = int(cfg["num_hidden_layers"])
    return {
        "kinds": layout(layers, int(cfg["mb_per_layer"])),
        "half": layers // 2,
        "heads": NH, "kv_heads": KVH,
        "head_dim": int(cfg.get("head_dim") or H // NH),
        "eps": float(cfg["layer_norm_eps"]),
        "window": int(cfg["sliding_window"]),
        "inner": int(cfg.get("mamba_expand", 2)) * H,
        "state": int(cfg.get("mamba_d_state", 16)),
        "conv": int(cfg.get("mamba_d_conv", 4)),
        "dt_rank": int(cfg.get("mamba_dt_rank") or math.ceil(H / 16)),
    }


def _ln(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def mamba1(d: Dict[str, Any], w, u):
    """``(Mixer(u), y, y * silu(z))`` over a whole sequence from a zero
    state, ``u`` [T, H] normed: the recurrence one token at a time."""
    I, N, K, R = d["inner"], d["state"], d["conv"], d["dt_rank"]
    T = u.shape[0]
    xz = u @ w("w_in")
    x, z = xz[:, :I], xz[:, I:]
    padded = jnp.concatenate([jnp.zeros((K - 1, I), F32), x])
    taps = w("w_conv")                                        # [I, K]
    x = jax.nn.silu(
        sum(padded[j : j + T] * taps[:, j] for j in range(K)) + w("b_conv")
    )
    rbc = x @ w("w_x")
    r, Bm, Cm = rbc[:, :R], rbc[:, R : R + N], rbc[:, R + N :]
    dt = jax.nn.softplus(r @ w("w_dt") + w("dt_bias"))        # [T, I]
    A = -jnp.exp(w("a_log"))                                  # [N, I]

    def token(S, t):
        x_t, B_t, C_t, dt_t = t
        S = jnp.exp(dt_t[None, :] * A) * S + B_t[:, None] * (dt_t * x_t)[None, :]
        return S, C_t @ S

    _, y = jax.lax.scan(token, jnp.zeros((N, I), F32), (x, Bm, Cm, dt))
    y = y + w("d_skip") * x
    gated = y * jax.nn.silu(z)
    return gated @ w("w_out"), y, gated


def differential(d, w, u, positions, depth, window, kv, variant):
    """``(Attn(u), (k, v))``: ``kv`` None projects the layer's own keys
    and values, else they are another layer's."""
    NH, KVH, Dh = d["heads"], d["kv_heads"], d["head_dim"]
    T = u.shape[0]
    q = (u @ w("wq") + w("bq")).reshape(T, NH // 2, 2, Dh)
    if kv is None:
        k = (u @ w("wk") + w("bk")).reshape(T, KVH // 2, 2, Dh)
        v = (u @ w("wv") + w("bv")).reshape(T, KVH // 2, 2 * Dh)
    else:
        k, v = kv
    rep = (NH // 2) // (KVH // 2)
    kk = jnp.repeat(k, rep, axis=1)                           # [T, NH/2, 2, Dh]
    vv = jnp.repeat(v, rep, axis=1)                           # [T, NH/2, 2 Dh]
    seen = positions[:, None] >= positions[None, :]
    if window:
        seen = seen & (positions[:, None] - positions[None, :] < window)
    init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (
        jnp.exp(jnp.sum(w("lambda_q1") * w("lambda_k1")))
        - jnp.exp(jnp.sum(w("lambda_q2") * w("lambda_k2"))) + init
    )
    if variant == "no_lambda":
        lam = 0.0
    a = []
    for c in range(2):
        scores = jnp.einsum("tnd,snd->nts", q[:, :, c], kk[:, :, c]) * Dh ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        a.append(jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), vv))
    o = a[0] - lam * a[1]                                     # [T, NH/2, 2 Dh]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + d["eps"])
    o = o * w("diff_norm") * (1.0 - init)
    return o.reshape(T, NH * Dh) @ w("wo") + w("bo"), (k, v)


def layer(d, layers, kind: str, m_index, f_index, depth: int, h, positions,
          carried, variant):
    """One block; ``carried`` = (m, (k, v)) of the layers that hand them
    on (None before them). Returns ``(h, carried)``."""
    m, kv = carried
    w = layer_weight(layers[kind], m_index)
    u = _ln(h, w("attn_norm"), w("attn_norm_b"), d["eps"])
    if kind == "mamba1":
        mixed, y, gated = mamba1(d, w, u)
        if depth == d["half"]:
            m = gated if variant == "memory_after_gate" else y
    elif kind == "gmu":
        mixed = (m * jax.nn.silu(u @ w("w_in"))) @ w("w_out")
    elif kind == "cross":
        window = d["window"] if variant == "cross_reads_window" else 0
        mixed, _ = differential(d, w, u, positions, depth, window, kv, variant)
    else:
        window = d["window"] if kind == "swa" else 0
        mixed, own = differential(d, w, u, positions, depth, window, None, variant)
        if kind == "attn":
            kv = own
    h = h + mixed
    f = layer_weight(layers["dense"], f_index)
    u = _ln(h, f("mlp_norm"), f("mlp_norm_b"), d["eps"])
    ffn = (jax.nn.silu(u @ f("w_gate")) * (u @ f("w_up"))) @ f("w_down")
    return h + ffn, (m, kv)


@functools.partial(jax.jit, static_argnums=(0, 2, 5, 9))
def _layer_jit(frozen, layers, kind, m_index, f_index, depth, h, positions,
               carried, variant):
    return layer(dict(frozen), layers, kind, m_index, f_index, depth, h,
                 positions, carried, variant)


def _head(embed, w, b, h, eps, block: int = 32768):
    x = _ln(h, w.astype(F32), b.astype(F32), eps)
    V = embed.shape[0]
    return jnp.concatenate([
        x @ embed[at : at + block].astype(F32).T for at in range(0, V, block)
    ], axis=-1)


def logits_at(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], *, variant: Optional[str] = None,
):
    """Full causal forward of ``ids`` ([T] ints); float32 logits
    ``[len(score_positions), V]`` at those positions. ``variant``: one
    of ``VARIANTS``, ANOTHER model (module docstring)."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} (one of {VARIANTS})")
    d = dims_of(cfg)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    seen: Dict[str, int] = {}
    I, NH, KVH, Dh = d["inner"], d["heads"], d["kv_heads"], d["head_dim"]
    T = ids.shape[0]
    # what the later layers read, zeros until its layer has run
    carried = (
        jnp.zeros((T, I), F32),
        (jnp.zeros((T, KVH // 2, 2, Dh), F32), jnp.zeros((T, KVH // 2, 2 * Dh), F32)),
    )
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for depth, kind in enumerate(d["kinds"]):
            h, carried = _layer_jit(
                frozen, params["layers"], kind, seen.get(kind, 0), depth,
                depth, h, positions, carried, variant,
            )
            seen[kind] = seen.get(kind, 0) + 1
        return _head(
            params["embed"], params["final_norm"], params["final_norm_b"],
            h[at], d["eps"],
        )
