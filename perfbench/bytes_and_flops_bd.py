"""Operations and bytes of a routed decoder that GENERATES BY BLOCKS
(``model_type`` ``sdar_moe``: Qwen3-MoE's layer under a mask that is
causal by blocks of ``block_length`` positions), from shapes alone. Kept
with the benchmark, beside ``bytes_and_flops.py`` (which counts a dense
FFN and one token a row a step), so that no later PR changes the
denominator of a roofline share.

A FORWARD of such a model is ``block_length`` positions a row over the
row's cached tokens, and there are two kinds: a DENOISING forward runs
the head over every position of the block and its logits are sampled
(``[rows x block, V]`` float32, written once and read once at the
least); a COMMIT forward keeps the block's K/V and has no head. The
needed bytes of a forward count each weight once (of a layer's experts
those some position chose), each row's cached K/V once for the whole
block (that is what the block form of the paged kernel is for) and what
the kind adds; activations, the router's sort and the block's own K/V
are left out, so a share computed from this cannot overstate.

A configuration is the dict of a ``configs/*.json`` file with the
``sdar_moe`` keys and ``block_length``. Everything here is a count;
nothing is measured.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if "block_length" not in cfg:
        raise ValueError("bytes_and_flops_bd: no block_length")
    if cfg.get("mlp_only_layers") or int(cfg.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("bytes_and_flops_bd: an FFN that is not routed")
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "H": H, "L": int(cfg["num_hidden_layers"]), "NH": NH,
        "KVH": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or H // NH), "V": int(cfg["vocab_size"]),
        "E": int(cfg["num_experts"]), "top_k": int(cfg["num_experts_per_tok"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "Bk": int(cfg["block_length"]),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def attention_params(d) -> int:
    """q, k, v and out projections, the two per-head QK-norm vectors and
    the layer's norm."""
    H, NH, KVH, Dh = d["H"], d["NH"], d["KVH"], d["Dh"]
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + 2 * Dh + H


def routed_ffn_params(d, experts: Optional[float] = None) -> float:
    """The router, ``experts`` experts (all unless said) and the FFN's
    norm."""
    E = d["E"] if experts is None else experts
    return d["H"] * d["E"] + E * 3 * d["H"] * d["Fm"] + d["H"]


def layer_params(cfg: Dict[str, Any]) -> int:
    d = dims(cfg)
    return int(attention_params(d) + routed_ffn_params(d))


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers, the final norm, the
    embedding and, when untied, the head."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(d["L"] * layer_params(cfg) + d["H"] + d["V"] * d["H"] + head)


def active_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters one token's forward uses (a row of the embedding
    aside): ``top_k`` experts a layer, and the head."""
    d = dims(cfg)
    return int(d["L"] * (
        attention_params(d) + routed_ffn_params(d, d["top_k"])
    ) + d["H"] + d["V"] * d["H"])


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over every layer."""
    d = dims(cfg)
    return 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes * d["L"]


def forward_bytes(
    cfg: Dict[str, Any], *, kind: str, batch: float, ctx: float,
    experts_touched: float, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one forward of ``batch`` rows' blocks must move
    (``kind``: "denoise" | "commit"; module docstring)."""
    d = dims(cfg)
    weights = d["L"] * (
        attention_params(d) + routed_ffn_params(d, experts_touched)
    ) + d["H"]
    kv = batch * ctx * kv_bytes_per_token(cfg, kv_dtype_bytes)
    total = weights * weight_dtype_bytes + kv
    if kind == "denoise":
        # the head, and the block's float32 logits written and read
        total += d["H"] * d["V"] * weight_dtype_bytes
        total += 2 * 4 * batch * d["Bk"] * d["V"]
    elif kind == "commit":
        # the block's K/V written
        total += batch * d["Bk"] * kv_bytes_per_token(cfg, kv_dtype_bytes)
    else:
        raise ValueError(f"bytes_and_flops_bd: kind {kind!r}")
    return float(total)


def block_kv_bytes(
    cfg: Dict[str, Any], *, batch: float, ctx: float, kv_dtype_bytes: int = 2
) -> float:
    """K/V one forward's attention must fetch: each row's ``ctx`` cached
    tokens ONCE for the block's positions, every layer."""
    return float(batch * ctx * kv_bytes_per_token(cfg, kv_dtype_bytes))


def token_matmul_flops(cfg: Dict[str, Any], head: bool = True) -> float:
    """Multiply-adds x 2 of one position's matrix products."""
    d = dims(cfg)
    H = d["H"]
    per_layer = (
        H * d["NH"] * d["Dh"] * 2 + 2 * H * d["KVH"] * d["Dh"]
        + H * d["E"] + d["top_k"] * 3 * H * d["Fm"]
    )
    return 2.0 * (d["L"] * per_layer + (H * d["V"] if head else 0))


def prefill_flops_per_row(cfg: Dict[str, Any], tokens: float) -> float:
    """What a prefill of a row of ``tokens`` tokens needs, unpadded:
    every position's products (the head on ONE position), and QK^T and
    PV over the keys the block mask lets it see (the causal half and
    half a block more)."""
    d = dims(cfg)
    seen = tokens * (tokens + d["Bk"]) / 2.0
    attn = 2.0 * 2.0 * d["NH"] * d["Dh"] * d["L"] * seen
    return (
        tokens * token_matmul_flops(cfg, head=False)
        + 2.0 * d["H"] * d["V"] + attn
    )
