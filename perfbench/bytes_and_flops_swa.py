"""Operations and bytes of a model whose attention layers are of two
kinds, from shapes alone: by ``layer_types`` a layer attends over a
sliding window of ``sliding_window`` positions or over the whole context,
every FFN is routed (``mlp_layer_types`` all "sparse"), and the head is
untied. Kept with the benchmark, beside ``bytes_and_flops.py`` (which
counts K/V over the whole context in EVERY layer, and so overstates what
a window layer reads: a share of the roofline computed from it would
pass 100 %), so that no later PR changes the denominator of a roofline
share.

A configuration is the dict of a ``configs/*.json`` file with the
``mellum`` keys. Everything here is a count; nothing is measured.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

KINDS = ("sliding_attention", "full_attention")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    types = list(cfg["layer_types"])
    L = int(cfg["num_hidden_layers"])
    unknown = sorted(set(types) - set(KINDS))
    if unknown or len(types) != L:
        raise ValueError(
            f"bytes_and_flops_swa: layer_types {unknown or len(types)} "
            f"for {L} layers of kinds {KINDS}"
        )
    if set(cfg.get("mlp_layer_types") or ["sparse"]) != {"sparse"}:
        raise ValueError("bytes_and_flops_swa: an FFN that is not routed")
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "H": H, "L": L, "NH": NH, "KVH": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or H // NH), "V": int(cfg["vocab_size"]),
        "E": int(cfg["num_experts"]), "top_k": int(cfg["num_experts_per_tok"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "window": int(cfg["sliding_window"]),
        "window_layers": types.count("sliding_attention"),
        "full_layers": types.count("full_attention"),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def attention_mixer_params(d) -> int:
    """q, k, v and out projections, the two per-head QK-norm vectors
    (assumed: the configuration file says so) and the layer's norm."""
    H, NH, KVH, Dh = d["H"], d["NH"], d["KVH"], d["Dh"]
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + 2 * Dh + H


def routed_ffn_params(d, experts: Optional[float] = None) -> float:
    """The router, ``experts`` experts (all of them unless said) and the
    layer's FFN norm."""
    E = d["E"] if experts is None else experts
    return d["H"] * d["E"] + E * 3 * d["H"] * d["Fm"] + d["H"]


def _trunk_params(d, experts: Optional[float]) -> float:
    return d["L"] * (
        attention_mixer_params(d) + routed_ffn_params(d, experts)
    ) + d["H"]                                       # final norm


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers, the final norm, the
    embedding and, when untied, the head."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d, None) + d["V"] * d["H"] + head)


def active_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters one token's forward pass uses (one row of the
    embedding aside)."""
    d = dims(cfg)
    return int(_trunk_params(d, d["top_k"]) + d["V"] * d["H"])


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every layer's mixer, router
    and norms and the output head in full, of each layer's experts the
    ``experts_touched`` some row chose. The embedding is read a row a
    token and left out."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def kv_bytes_per_token_layer(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer."""
    d = dims(cfg)
    return 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def kv_bytes_per_sequence(
    cfg: Dict[str, Any], ctx: int, kv_dtype_bytes: int = 2,
    one_pool: bool = False,
) -> int:
    """K/V a sequence of ``ctx`` tokens holds: the whole context in the
    full layers, at most the window in the window layers (``one_pool``:
    the whole context in every layer, what one pool for both kinds
    keeps)."""
    d = dims(cfg)
    held = ctx if one_pool else min(ctx, d["window"])
    return kv_bytes_per_token_layer(cfg, kv_dtype_bytes) * (
        d["full_layers"] * ctx + d["window_layers"] * held
    )


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, kv_tokens_full: float,
    kv_tokens_window: float, experts_touched: float,
    weight_dtype_bytes: int = 2, kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once (of the experts those touched), each row's cached K/V
    once, ``kv_tokens_full`` tokens a full layer and
    ``kv_tokens_window`` a window layer (the means the spans report:
    the context, and the context at most the window), and the new
    token's written in every layer. Activations, logits, the router's
    sort and sampling are left out, so a share computed from this is a
    lower bound on the traffic and cannot overstate the roofline."""
    d = dims(cfg)
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token_layer(cfg, kv_dtype_bytes) * (
        d["full_layers"] * (kv_tokens_full + 1.0)
        + d["window_layers"] * (kv_tokens_window + 1.0)
    )
    return float(weights + kv)


def forward_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    """Multiply-adds x 2 for one token attending over ``ctx`` cached
    tokens (a window layer over at most the window): the layers' and
    head's matrix multiplications (of a layer's FFN: the router and
    ``num_experts_per_tok`` experts), and QK^T and PV."""
    d = dims(cfg)
    H = d["H"]
    matmul = d["L"] * (
        H * d["NH"] * d["Dh"] * 2 + 2 * H * d["KVH"] * d["Dh"]
        + H * d["E"] + d["top_k"] * 3 * H * d["Fm"]
    ) + H * d["V"]
    attn = 2.0 * d["NH"] * d["Dh"] * (
        d["full_layers"] * ctx + d["window_layers"] * min(ctx, d["window"])
    )
    return 2.0 * (matmul + attn)
