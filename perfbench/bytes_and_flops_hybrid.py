"""Operations and bytes of a model whose layers are of several kinds,
from shapes alone: a mixer a layer by ``layer_types`` (a gated short
convolution or GQA attention), a dense FFN for the first
``num_dense_layers`` layers and a routed one after, K/V over the
attention layers only, and the conv layers' per-sequence state. Kept
with the benchmark, beside ``bytes_and_flops.py`` (which counts an
attention block and one kind of FFN in every layer and K/V over
``num_hidden_layers``, and so overstates this family's bytes), so that
no later PR changes the denominator of a roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
``lfm2_moe`` keys. Everything here is a count; nothing is measured.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

KINDS = ("conv", "full_attention")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    types = list(cfg["layer_types"])
    L = int(cfg["num_hidden_layers"])
    unknown = sorted(set(types) - set(KINDS))
    if unknown or len(types) != L:
        raise ValueError(
            f"bytes_and_flops_hybrid: layer_types {unknown or len(types)} "
            f"for {L} layers of kinds {KINDS}"
        )
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dense = int(cfg["num_dense_layers"])
    return {
        "H": H, "L": L, "NH": NH, "KVH": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or H // NH),
        "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
        "K": int(cfg["conv_L_cache"]),
        "E": int(cfg["num_experts"]), "top_k": int(cfg["num_experts_per_tok"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "conv_layers": types.count("conv"),
        "attn_layers": types.count("full_attention"),
        "dense_layers": min(dense, L), "routed_layers": max(L - dense, 0),
        "tied": bool(cfg.get("tie_word_embeddings", True)),
    }


def conv_mixer_params(d) -> int:
    """in_proj [H, 3H], the depthwise taps [H, K], out_proj [H, H] and
    the layer's operator norm."""
    H = d["H"]
    return H * 3 * H + H * d["K"] + H * H + H


def attention_mixer_params(d) -> int:
    """q, k, v and out projections, the two QK-norm vectors and the
    layer's operator norm."""
    H, NH, KVH, Dh = d["H"], d["NH"], d["KVH"], d["Dh"]
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + 2 * Dh + H


def dense_ffn_params(d) -> int:
    return 3 * d["H"] * d["F"] + d["H"]


def routed_ffn_params(d, experts: Optional[float] = None) -> float:
    """The router, the selection bias, ``experts`` experts (all of them
    unless said) and the layer's FFN norm."""
    E = d["E"] if experts is None else experts
    return d["H"] * d["E"] + d["E"] + E * 3 * d["H"] * d["Fm"] + d["H"]


def _trunk_params(d, experts: Optional[float]) -> float:
    return (
        d["conv_layers"] * conv_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
        + d["dense_layers"] * dense_ffn_params(d)
        + d["routed_layers"] * routed_ffn_params(d, experts)
        + d["H"]                                    # final norm
    )


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers by kind, the final
    norm, the embedding and, when untied, the head."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d, None) + d["V"] * d["H"] + head)


def active_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters one token's forward pass uses."""
    d = dims(cfg)
    return int(_trunk_params(d, d["top_k"]) + d["V"] * d["H"])


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every layer and the output
    head (the tied table) in full, of each routed layer's experts the
    ``experts_touched`` some row chose."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over the ATTENTION layers."""
    d = dims(cfg)
    return d["attn_layers"] * 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def state_bytes_per_sequence(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """The conv layers' K-1 columns of state a sequence keeps."""
    d = dims(cfg)
    return d["conv_layers"] * (d["K"] - 1) * d["H"] * dtype_bytes


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    experts_touched: float, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once (of the experts those touched), each row's cached K/V
    once and the new token's written, each row's conv state read and
    written. Activations, logits, the router's sort and sampling are
    left out, so a share computed from this is a lower bound on the
    traffic and cannot overstate the roofline. The selection bias is
    float32 and counted at the weights' width: 2 KB a model."""
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    state = 2.0 * batch * state_bytes_per_sequence(cfg, kv_dtype_bytes)
    return float(weights + kv + state)


def forward_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    """Multiply-adds x 2 for one token attending over ``ctx`` cached
    tokens: the layers' and head's matrix multiplications (of a routed
    layer: the router and ``num_experts_per_tok`` experts), the K-tap
    convolutions, and QK^T and PV in the attention layers."""
    d = dims(cfg)
    H = d["H"]
    matmul = (
        d["conv_layers"] * (H * 3 * H + H * H + H * d["K"])
        + d["attn_layers"] * (H * d["NH"] * d["Dh"] * 2 + 2 * H * d["KVH"] * d["Dh"])
        + d["dense_layers"] * 3 * H * d["F"]
        + d["routed_layers"] * (H * d["E"] + d["top_k"] * 3 * H * d["Fm"])
        + H * d["V"]
    )
    attn = 2.0 * d["attn_layers"] * d["NH"] * d["Dh"] * ctx
    return 2.0 * (matmul + attn)
