"""Operations and bytes of a model whose blocks are ONE sublayer each,
from shapes alone: by ``hybrid_override_pattern`` a block is a Mamba-2
mixer (``M``), GQA attention (``*``) or a routed FFN (``E``) of
two-matrix experts beside one shared expert, of which this chip holds
``n_routed_experts`` of the router's ``share.experts_published``. Kept
with the benchmark, beside ``bytes_and_flops_ssm.py`` (a dense FFN in
every block, no experts) and ``bytes_and_flops_hybrid.py`` (three-matrix
experts, every one held, no state), so that no later PR changes the
denominator of a roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
``nemotron_h`` keys. Everything here is a count; nothing is measured.
No width is padded: an expert is 2 x 2,688 x 1,856 whatever tile a
kernel would round it to.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

SYMBOLS = ("M", "*", "E")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    pattern = str(cfg["hybrid_override_pattern"])
    L = int(cfg["num_hidden_layers"])
    unknown = sorted(set(pattern) - set(SYMBOLS))
    if unknown or len(pattern) != L:
        raise ValueError(
            f"bytes_and_flops_ssm_moe: pattern symbols {unknown or len(pattern)} "
            f"for {L} blocks of kinds {SYMBOLS}"
        )
    heads, d_head = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    I = heads * d_head
    held = int(cfg["n_routed_experts"])
    return {
        "H": int(cfg["hidden_size"]), "L": L, "V": int(cfg["vocab_size"]),
        "NH": int(cfg["num_attention_heads"]),
        "KVH": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "heads": heads, "I": I, "N": N, "G": G, "K": int(cfg["conv_kernel"]),
        "conv_dim": I + 2 * G * N,
        "E_held": held,
        "E_router": int((cfg.get("share") or {}).get("experts_published", held)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg.get("n_shared_experts", 1)),
        "mamba_blocks": pattern.count("M"),
        "attn_blocks": pattern.count("*"),
        "moe_blocks": pattern.count("E"),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def mamba_block_params(d) -> int:
    """in_proj [H, 2I + 2GN + heads], the conv's taps and bias, dt_bias,
    A_log and D a head, the gated norm's weight, out_proj, the block's
    norm."""
    H, I = d["H"], d["I"]
    return (
        H * (I + d["conv_dim"] + d["heads"]) + d["conv_dim"] * (d["K"] + 1)
        + 3 * d["heads"] + I + I * H + H
    )


def attention_block_params(d) -> int:
    """q, k, v and out projections (no biases, no QK norm) and the
    block's norm."""
    H, NH, KVH, Dh = d["H"], d["NH"], d["KVH"], d["Dh"]
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + H


def expert_params(d) -> int:
    """One routed expert: two matrices."""
    return 2 * d["H"] * d["Fm"]


def routed_block_params(d, experts: Optional[float] = None) -> float:
    """The router over every published expert with its selection bias,
    ``experts`` experts (the held ones unless said), the shared expert
    and the block's norm."""
    E = d["E_held"] if experts is None else experts
    return (
        d["H"] * d["E_router"] + d["E_router"] + E * expert_params(d)
        + 2 * d["H"] * d["Fs"] + d["H"]
    )


def _trunk_params(d, experts: Optional[float]) -> float:
    return (
        d["mamba_blocks"] * mamba_block_params(d)
        + d["attn_blocks"] * attention_block_params(d)
        + d["moe_blocks"] * routed_block_params(d, experts)
        + d["H"]                                    # final norm
    )


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the blocks by kind (of a routed
    block the HELD experts), the final norm, the embedding and, when
    untied, the head, each over the held slice of the vocabulary."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d, None) + d["V"] * d["H"] + head)


def active_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters one token's forward pass uses ON THIS CHIP, on
    average: of a routed block's experts ``num_experts_per_tok`` times
    the held share of the router's (one row of the embedding aside)."""
    d = dims(cfg)
    mine = d["top_k"] * d["E_held"] / d["E_router"]
    return int(_trunk_params(d, mine) + d["V"] * d["H"])


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every block's mixer,
    router, shared expert and norms and the output head in full, of each
    routed block's held experts the ``experts_touched`` some row chose.
    The embedding is read a row a token and left out."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over the ATTENTION blocks."""
    d = dims(cfg)
    return d["attn_blocks"] * 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def state_bytes_per_sequence(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a sequence keeps over the Mamba-2 blocks: a state
    [heads, d_head, N] and the conv's K-1 columns of [x | B | C]."""
    d = dims(cfg)
    per_block = d["I"] * d["N"] + (d["K"] - 1) * d["conv_dim"]
    return d["mamba_blocks"] * per_block * dtype_bytes


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    state_rows: float, experts_touched: float, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2, state_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once (of the held experts those touched), each row's cached
    K/V once and the new token's written, and the state of each of the
    ``state_rows`` rows it advances READ once. The state's write is left
    out (a program that fuses several steps may write it once for all of
    them), as are activations, logits, the router's sort and sampling:
    a share computed from this is a lower bound on the traffic and
    cannot overstate the roofline. The float32 leaves (``dt_bias``,
    ``A_log``, ``D``, the selection bias) are counted at the weights'
    width: 6 KB a model."""
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    state = state_rows * state_bytes_per_sequence(cfg, state_dtype_bytes)
    return float(weights + kv + state)


def forward_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    """Multiply-adds x 2 for one token attending over ``ctx`` cached
    tokens ON THIS CHIP: the blocks' and head's matrix multiplications
    (of a routed block the router, the shared expert and
    ``num_experts_per_tok`` experts times the held share), the K-tap
    convolutions, the state's update and read (2 I N each), and QK^T
    and PV in the attention blocks."""
    d = dims(cfg)
    H, I = d["H"], d["I"]
    mine = d["top_k"] * d["E_held"] / d["E_router"]
    matmul = (
        d["mamba_blocks"] * (
            H * (I + d["conv_dim"] + d["heads"]) + I * H
            + d["conv_dim"] * d["K"] + 2 * I * d["N"]
        )
        + d["attn_blocks"] * (H * d["NH"] * d["Dh"] * 2 + 2 * H * d["KVH"] * d["Dh"])
        + d["moe_blocks"] * (
            H * d["E_router"] + 2 * H * d["Fs"] + mine * expert_params(d)
        )
        + H * d["V"]
    )
    attn = 2.0 * d["attn_blocks"] * d["NH"] * d["Dh"] * ctx
    return 2.0 * (matmul + attn)
