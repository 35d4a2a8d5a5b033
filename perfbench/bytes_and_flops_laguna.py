"""Operations and bytes of a model whose layer KINDS differ in their
query heads, from shapes alone (``model_type`` ``laguna``): by
``layer_types`` a layer attends over a sliding window with
``num_attention_heads_per_layer`` = 72 query heads or over the whole
context with 48, both over ``num_key_value_heads`` KV heads, each query
head under a gate of its own; by ``mlp_layer_types`` its FFN is dense or
routed beside ONE gated shared expert; of the routed experts this chip
holds ``share.experts_held`` (``share``: one chip's part of a layer) and
of the vocabulary the rows the file keeps. Kept with the benchmark, so
that no later PR changes the denominator of a roofline share
(``LAGUNA_LAYERS.md`` says how each is counted).

A configuration is the dict of a ``configs/*.json`` file with the
``laguna`` keys. Everything here is a count; nothing is measured.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

KINDS = ("sliding_attention", "full_attention")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    types = list(cfg["layer_types"])
    ffns = list(cfg["mlp_layer_types"])
    heads = [int(n) for n in cfg["num_attention_heads_per_layer"]]
    L = int(cfg["num_hidden_layers"])
    if set(types) - set(KINDS) or not len(types) == len(ffns) == len(heads) == L:
        raise ValueError(
            f"bytes_and_flops_laguna: {len(types)} layer_types, {len(ffns)} "
            f"mlp_layer_types, {len(heads)} head counts for {L} layers of "
            f"kinds {KINDS}"
        )
    if set(ffns) - {"dense", "sparse"}:
        raise ValueError("bytes_and_flops_laguna: an FFN neither dense nor sparse")
    by_kind = {k: {h for t, h in zip(types, heads) if t == k} for k in KINDS}
    if any(len(v) > 1 for v in by_kind.values()):
        raise ValueError("bytes_and_flops_laguna: a kind with two head counts")
    share = cfg.get("share") or {}
    E = int(share.get("experts_published", cfg["num_experts"]))
    return {
        "H": int(cfg["hidden_size"]), "L": L,
        "KVH": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "V": int(cfg["vocab_size"]), "F": int(cfg["intermediate_size"]),
        "E": E, "held": int(share.get("experts_held", E)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["shared_expert_intermediate_size"]),
        "window": int(cfg["sliding_window"]),
        "window_layers": types.count("sliding_attention"),
        "full_layers": types.count("full_attention"),
        "NH_window": max(by_kind["sliding_attention"], default=0),
        "NH_full": max(by_kind["full_attention"], default=0),
        "dense_layers": ffns.count("dense"),
        "routed_layers": ffns.count("sparse"),
    }


def mixer_params(d, heads: int) -> int:
    """q and out at the kind's heads, k and v, the gate a head, the two
    per-head QK-norm vectors (assumed) and the layer's norm."""
    H, KVH, Dh = d["H"], d["KVH"], d["Dh"]
    return 2 * H * heads * Dh + 2 * H * KVH * Dh + H * heads + 2 * Dh + H


def dense_ffn_params(d) -> int:
    return 3 * d["H"] * d["F"] + d["H"]


def expert_params(d) -> int:
    return 3 * d["H"] * d["Fm"]


def routed_rest_params(d) -> int:
    """A routed FFN outside its experts: the router, the shared expert,
    its gate and the layer's norm."""
    H = d["H"]
    return H * d["E"] + 3 * H * d["Fs"] + H + H


def _trunk_params(d, experts: float) -> float:
    """The layers with ``experts`` experts a routed layer, and the final
    norm."""
    return (
        d["window_layers"] * mixer_params(d, d["NH_window"])
        + d["full_layers"] * mixer_params(d, d["NH_full"])
        + d["dense_layers"] * dense_ffn_params(d)
        + d["routed_layers"] * (routed_rest_params(d) + experts * expert_params(d))
        + d["H"]
    )


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers with the experts
    HELD, the final norm, the embedding and the untied head over the
    rows the file keeps."""
    d = dims(cfg)
    return int(_trunk_params(d, d["held"]) + 2 * d["V"] * d["H"])


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every mixer (each kind at
    its own heads, with its gate), the dense FFN, every routed layer's
    router, shared expert and gate, of its held experts the
    ``experts_touched`` some row chose, the norms and the head's slice.
    The embedding is read a row a token and left out."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def kv_bytes_per_token_layer(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer (both kinds keep the
    same KV heads)."""
    d = dims(cfg)
    return 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def decode_kv_bytes(
    cfg: Dict[str, Any], *, batch: float, kv_tokens_full: float,
    kv_tokens_window: float, kv_dtype_bytes: int = 2, written: float = 1.0,
) -> float:
    """K/V one decode step over ``batch`` rows must move, by kind:
    ``kv_tokens_full`` cached tokens a full layer and
    ``kv_tokens_window`` a window layer (the means the ``decode_window``
    spans report: the context, and the context at most the window), and
    the new token's ``written`` in every layer (0: the reads alone, what
    the paged kernel fetches)."""
    d = dims(cfg)
    return float(batch * kv_bytes_per_token_layer(cfg, kv_dtype_bytes) * (
        d["full_layers"] * (kv_tokens_full + written)
        + d["window_layers"] * (kv_tokens_window + written)
    ))


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, kv_tokens_full: float,
    kv_tokens_window: float, experts_touched: float,
    weight_dtype_bytes: int = 2, kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step must move: the weights once
    (``decode_weight_params``) and the K/V by kind (``decode_kv_bytes``).
    Activations, logits, the router's sort and sampling are left out, so
    a share computed from this is a lower bound on the traffic and
    cannot overstate the roofline."""
    return float(
        decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
        + decode_kv_bytes(
            cfg, batch=batch, kv_tokens_full=kv_tokens_full,
            kv_tokens_window=kv_tokens_window, kv_dtype_bytes=kv_dtype_bytes,
        )
    )


def causal_pairs(n: float, window: Optional[int] = None) -> float:
    """(query, key) pairs of a row of ``n`` tokens under the causal
    mask, a query seeing at most ``window`` keys."""
    if window is None or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * window


def prefill_flops_per_row(cfg: Dict[str, Any], n: float) -> float:
    """Multiply-adds x 2 a prefilled row of ``n`` REAL tokens needs on
    this chip: every position's projections at its layer kind's heads
    (q, k, v, the gate, out), the dense FFN, every routed layer's router,
    gated shared expert and the share of its ``top_k`` experts an even
    router lands on the held ones (``top_k x held / experts``), QK^T and
    PV over the pairs the mask shows (a window layer: at most the
    window), and the head's slice on ONE position. Nothing padded."""
    d = dims(cfg)
    H, Dh, KVH = d["H"], d["Dh"], d["KVH"]

    def mixer(heads):
        return 2 * H * heads * Dh + 2 * H * KVH * Dh + H * heads

    per_token = (
        d["window_layers"] * mixer(d["NH_window"])
        + d["full_layers"] * mixer(d["NH_full"])
        + d["dense_layers"] * 3 * H * d["F"]
        + d["routed_layers"] * (
            H * d["E"] + 3 * H * d["Fs"] + H
            + d["top_k"] * d["held"] / d["E"] * 3 * H * d["Fm"]
        )
    )
    attention = 2.0 * Dh * (
        d["full_layers"] * d["NH_full"] * causal_pairs(n)
        + d["window_layers"] * d["NH_window"] * causal_pairs(n, d["window"])
    )
    return 2.0 * (n * per_token + attention + H * d["V"])


def prefill_flops(cfg: Dict[str, Any], rows: Sequence[float]) -> float:
    """A dispatch's: each row at its own length."""
    return float(sum(prefill_flops_per_row(cfg, float(n)) for n in rows if n > 0))
