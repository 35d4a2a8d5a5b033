"""Latent-attention decoder under LEARNED SPARSE attention, with routed
layers (``model_type`` ``glm_moe_dsa``: GLM-5; DeepSeek-V3's key set
plus the four ``index_*`` keys of DeepSeek-V3.2's published lightning
indexer), plain ``jax.numpy`` in float32.

Written from the published ``config.json`` keys and the family's
published descriptions (DeepSeek-V2, "Multi-head Latent Attention";
DeepSeek-V3, the sigmoid router with a selection bias; DeepSeek-V3.2,
"DeepSeek Sparse Attention": the lightning indexer and the top-k token
selection), independent of ``sutro_tpu/``: no kernels, no cache, no
batching, no absorbed products, no gather of selected rows, no sort of
rows by expert. A full causal forward in the EXPANDED form; the
selection is a mask on it. ``h`` is the residual stream; everything that
``mla_moe.py`` states (the blocks, the latent attention's projections,
the dense and routed FFNs, the share) holds here, at this file's widths
(``v_head_dim`` need not equal ``qk_nope_head_dim``), and is computed by
that module's functions where they are the same. What this family adds,
in every layer, ``u`` [T, H] the normed input of the attention and
``c_q`` the normed query latent:

    q_I  = c_q W_Iqb -> [T, NHi, Di]          index_n_heads x index_head_dim
    k_I  = LayerNorm(u W_Ik) * g + b -> [T, Di]    ONE key a token, eps 1e-6
    rope (the first qk_rope_head_dim of q_I and of k_I, the attention's
         theta and positions, the pair (2i, 2i+1):
         ``indexer_rope_interleave`` true)
    w    = u W_Iw / sqrt(NHi) / sqrt(Di) -> [T, NHi]
    I(t, s) = sum_j w_j(t) * relu(q_I_j(t) . k_I(s)),          s <= t
    S_t  = the index_topk positions s <= t of largest I(t, .), equal
           values to the lower position; every s <= t while
           t + 1 <= index_topk
    score_h(t, s) as in mla_moe.py; the softmax runs over s in S_t ONLY.

Weights arrive in the layout the system serves them in: ``layers["mla"]``
as ``mla_moe.py`` says plus ``w_iqb`` [L, Rq, NHi * Di], ``w_ik`` [L, H,
Di], ``ik_norm`` and ``ik_bias`` [L, Di], ``w_iw`` [L, H, NHi].

Departures from the published description (``dsa_moe.md`` has each with
its reason): the share of a deployment the file states; the
multi-token-prediction block is not computed; the published inference
code turns ``q_I`` and ``k_I`` by a Hadamard matrix and keeps ``k_I`` in
float8 with a scale a token: the turn is orthogonal (no dot product
changes) and exists for the cast, and nothing is cast here, so neither
is done; the LayerNorm's eps is assumed 1e-6. The sums run a block of
queries and a group of heads at a time (the same sums: 16,384 positions
would otherwise hold [NH, T, T] scores).

The tests' and the tools' switches compute ANOTHER model: a config
``index_topk`` past every position attends densely; ``select="lowest"``
keeps the index_topk positions of SMALLEST ``I`` (the wrong rows).

What it refuses rather than guesses: what ``mla_moe.py`` refuses, and
``indexer_rope_interleave`` false, a ``rope_parameters.rope_type`` other
than ``default``, an ``index_head_dim`` under ``qk_rope_head_dim``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .mla_moe import _held, rope_interleaved, routed_ffn, swiglu
from .qwen3_dense import F32, _embed, _head, _rms, layer_weight

ROUTED = True
TIE_MARGIN = 0.02
QUERY_BLOCK = 256
HEAD_GROUP = 8
INDEX_NORM_EPS = 1e-6


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs; raises on what it does not follow."""
    rope = cfg.get("rope_parameters") or {}
    refuse = {
        "rope_scaling": cfg.get("rope_scaling") is not None,
        "rope_type other than default": rope.get("rope_type") != "default",
        "rope_interleave false": cfg.get("rope_interleave") is not True,
        "indexer_rope_interleave false":
            cfg.get("indexer_rope_interleave") is not True,
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
        "index_head_dim under qk_rope_head_dim":
            int(cfg["index_head_dim"]) < int(cfg["qk_rope_head_dim"]),
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise NotImplementedError(
            f"reference dsa_moe does not implement: {', '.join(bad)}"
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
        "theta": float(rope["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "index_heads": int(cfg["index_n_heads"]),
        "index_dim": int(cfg["index_head_dim"]),
        "index_topk": int(cfg["index_topk"]),
        "experts": int(share.get("experts_published", held)),
        "first": int(share.get("first_expert", 0)),
        "held": held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
    }
    if not 1 <= dims["top_k"] <= dims["experts"]:
        raise ValueError("dsa_moe: num_experts_per_tok outside 1..experts")
    if dims["first"] + dims["held"] > dims["experts"]:
        raise ValueError("dsa_moe: the held experts are not among the router's")
    if dims["index_topk"] < 1:
        raise ValueError("dsa_moe: index_topk must be at least 1")
    return dims


def _layer_norm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g + b


def _part_rope(d, x, positions):
    """The rotary embedding on the first ``rope`` elements of x [T, ..., D]."""
    Dr = d["rope"]
    return jnp.concatenate(
        [rope_interleaved(x[..., :Dr], positions, d["theta"]), x[..., Dr:]],
        axis=-1,
    )


def index_keys(d: Dict[str, Any], w, u, positions):
    """``k_I`` [T, Di]: what a token leaves behind for later queries'
    indexers (beside its latent row, ``mla_moe.latents``)."""
    k = _layer_norm(u @ w("w_ik"), w("ik_norm"), w("ik_bias"), INDEX_NORM_EPS)
    return _part_rope(d, k, positions)


def _by_query_blocks(fn, per_query, T: int):
    """``fn`` over blocks of QUERY_BLOCK queries (``per_query``: arrays
    [T, ...], zero-padded to whole blocks), the results [T, ...]. One
    traced body, a block at a time (``lax.map``): the same sums."""
    nb = -(-T // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - T
    split = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (nb, QUERY_BLOCK) + a.shape[1:]
        )
        for a in per_query
    )
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return out.reshape((nb * QUERY_BLOCK,) + out.shape[2:])[:T]


def selection(d: Dict[str, Any], w, u, c_q, positions,
              select: str = "highest"):
    """``S`` [T, T] bool: row t keeps the ``index_topk`` positions
    ``s <= t`` of largest ``I(t, s)`` (``select`` "lowest": of smallest,
    the tools' control), equal values to the lower position; every
    ``s <= t`` while there are no more than that."""
    T, k = u.shape[0], d["index_topk"]
    if k >= T:
        return positions[:, None] >= positions[None, :]
    NHi, Di = d["index_heads"], d["index_dim"]
    q = _part_rope(d, (c_q @ w("w_iqb")).reshape(T, NHi, Di), positions)
    keys = index_keys(d, w, u, positions)
    weight = (u @ w("w_iw")) / jnp.sqrt(F32(NHi)) / jnp.sqrt(F32(Di))

    def block(q, weight, pos):
        s = jnp.einsum("tnd,sd->tns", q, keys)
        score = jnp.einsum("tns,tn->ts", jax.nn.relu(s), weight)   # I
        if select == "lowest":
            score = -score
        ok = pos[:, None] >= positions[None, :]
        score = jnp.where(ok, score + 0.0, -jnp.inf)
        kth = jax.lax.top_k(score, k)[0][:, -1:]          # the k-th largest
        above = ok & (score > kth)
        equal = ok & (score == kth)
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))

    return _by_query_blocks(block, (q, weight, positions), T)


def attention(d: Dict[str, Any], w, u, positions, select: str = "highest",
              return_selection: bool = False):
    """``attn(u)`` over a whole sequence, ``u`` [T, H] normed, EXPANDED:
    K and V a head at every position, the softmax over the selected
    pairs. A group of heads and a block of queries at a time."""
    NH, Dn, Dr, Dv = d["heads"], d["nope"], d["rope"], d["v_dim"]
    T = u.shape[0]
    c_q = _rms(u @ w("w_qa"), w("q_norm"), d["eps"])
    keep = selection(d, w, u, c_q, positions, select)
    if return_selection:
        return keep
    kva = u @ w("w_kva")
    c_kv = _rms(kva[:, : d["kv_rank"]], w("kv_norm"), d["eps"])
    k_pe = rope_interleaved(kva[:, d["kv_rank"]:], positions, d["theta"])
    w_qb = w("w_qb").reshape(-1, NH, Dn + Dr)
    w_kvb = w("w_kvb").reshape(-1, NH, Dn + Dv)
    heads = []
    for n0 in range(0, NH, HEAD_GROUP):
        n1 = min(n0 + HEAD_GROUP, NH)
        q = jnp.einsum("tc,cnd->tnd", c_q, w_qb[:, n0:n1])
        q_pe = rope_interleaved(q[..., Dn:], positions, d["theta"])
        kv = jnp.einsum("tc,cnd->tnd", c_kv, w_kvb[:, n0:n1])
        k_nope, v = kv[..., :Dn], kv[..., Dn:]

        def block(q_nope, q_pe, keep, k_nope=k_nope, v=v):
            scores = (
                jnp.einsum("tnd,snd->nts", q_nope, k_nope)
                + jnp.einsum("tnd,sd->nts", q_pe, k_pe)
            ) / jnp.sqrt(F32(Dn + Dr))
            # a padded query of the last block keeps nothing: zeros
            p = jax.nn.softmax(
                jnp.where(keep[None], scores, -jnp.inf), axis=-1
            )
            return jnp.einsum("nts,snd->tnd", jnp.nan_to_num(p), v)

        heads.append(_by_query_blocks(block, (q[..., :Dn], q_pe, keep), T))
    return jnp.concatenate(heads, axis=1).reshape(T, NH * Dv) @ w("wo")


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7, 8))
def _layer_jit(frozen, routed, layers, index, f_index, h_pos, shared, select,
               part):
    """One layer over a whole sequence: (h [T, H], gap [T], inf for a
    layer that does not route). ``part``: "both"; "ffn" alone (a layer's
    FFN output on a given input) or "selection" (the layer's S [T, T] on
    a given input), the tests' views."""
    d = dict(frozen)
    h, positions = h_pos
    gap = jnp.full((h.shape[0],), jnp.inf, F32)
    if part != "ffn":
        w = layer_weight(layers["mla"], index)
        a = attention(
            d, w, _rms(h, w("attn_norm"), d["eps"]), positions, select,
            return_selection=part == "selection",
        )
        if part == "selection":
            return a, gap
        h = h + a
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


def logits_and_near_ties(
    cfg: Dict[str, Any], params: Dict[str, Any], ids: Sequence[int],
    score_positions: Sequence[int], *,
    experts: Optional[Tuple[int, int]] = None, shared: bool = True,
    select: str = "highest", return_hidden: bool = False,
    return_selection: bool = False,
):
    """Full causal forward of ``ids`` ([T] ints): float32 logits
    ``[len(score_positions), V]`` and, per scored position, the number
    of routed layers whose selection of EXPERTS there was a near tie.
    ``experts``, ``shared`` and ``select`` are the tests' and the tools':
    a share of the stack's experts, the shared expert left out, the
    wrong rows. ``return_hidden`` gives the residual stream before the
    final norm in the logits' place; ``return_selection`` every layer's
    ``S`` [layers, T, T] bool."""
    d = dims_of(cfg)
    params, d = _held(params, experts, d)
    frozen = tuple(sorted(d.items()))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    at = jnp.asarray(score_positions, jnp.int32)
    ties = jnp.zeros(at.shape, jnp.int32)
    kept = []
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], ids)
        for i in range(d["layers"]):
            routed = i >= d["dense_layers"]
            args = (
                frozen, routed, params["layers"], i,
                i - d["dense_layers"] if routed else i, (h, positions),
                shared, select,
            )
            if return_selection:
                kept.append(_layer_jit(*args, "selection")[0])
            h, gap = _layer_jit(*args, "both")
            ties = ties + (gap[at] < TIE_MARGIN)
        if return_selection:
            return jnp.stack(kept), ties
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
            shared, "highest", "ffn",
        )
    return y
