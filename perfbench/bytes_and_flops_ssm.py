"""Operations and bytes of a model with Mamba-2 layers beside attention
layers (``model_type`` ``granitemoehybrid``, dense members), from shapes
alone: a mixer a layer by ``layer_types`` (``mamba`` or ``attention``),
one dense SwiGLU FFN a layer, K/V over the attention layers only, and
the mamba layers' per-sequence state: a matrix a head and the conv's
last columns. Kept with the benchmark, beside ``bytes_and_flops.py``
(which counts an attention block in every layer and K/V over
``num_hidden_layers``, and knows no state), so that no later PR changes
the denominator of a roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
published ``granitemoehybrid`` keys. Everything here is a count; nothing
is measured.
"""

from __future__ import annotations

from typing import Any, Dict

KINDS = ("mamba", "attention")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    types = list(cfg["layer_types"])
    L = int(cfg["num_hidden_layers"])
    unknown = sorted(set(types) - set(KINDS))
    if unknown or len(types) != L or int(cfg.get("num_local_experts") or 0):
        raise ValueError(
            f"bytes_and_flops_ssm: layer_types {unknown or len(types)} for "
            f"{L} layers of kinds {KINDS}, and no routed experts"
        )
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    heads, d_head = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    N, G = int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"])
    return {
        "H": H, "L": L, "NH": NH, "KVH": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or H // NH),
        "F": int(cfg["shared_intermediate_size"]), "V": int(cfg["vocab_size"]),
        "heads": heads, "I": heads * d_head, "N": N, "G": G,
        "conv_dim": heads * d_head + 2 * G * N, "K": int(cfg["mamba_d_conv"]),
        "mamba_layers": types.count("mamba"),
        "attn_layers": types.count("attention"),
        "tied": bool(cfg.get("tie_word_embeddings", True)),
    }


def mamba_mixer_params(d) -> int:
    """in_proj [H, 2I + 2GN + heads], the depthwise taps and their bias,
    ``dt_bias``, ``A_log`` and ``D`` a head, the gate norm, out_proj
    [I, H] and the layer's input norm."""
    H, I = d["H"], d["I"]
    return (
        H * (I + d["conv_dim"] + d["heads"])
        + d["conv_dim"] * d["K"] + d["conv_dim"]
        + 3 * d["heads"] + I + I * H + H
    )


def attention_mixer_params(d) -> int:
    """q, k, v and out projections (no biases, no QK-norm) and the
    layer's input norm."""
    H, NH, KVH, Dh = d["H"], d["NH"], d["KVH"], d["Dh"]
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + H


def ffn_params(d) -> int:
    return 3 * d["H"] * d["F"] + d["H"]


def _trunk_params(d) -> int:
    return (
        d["mamba_layers"] * mamba_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
        + d["L"] * ffn_params(d)
        + d["H"]                                    # final norm
    )


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers by kind, the final
    norm, the embedding and, when untied, the head."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d) + d["V"] * d["H"] + head)


def active_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters one token's forward pass uses: all of them (dense)."""
    return param_count(cfg)


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over the ATTENTION layers."""
    d = dims(cfg)
    return d["attn_layers"] * 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def state_bytes_per_sequence(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a sequence keeps over the mamba layers: a state
    [heads, d_head, N] and the conv's K-1 columns of [x | B | C]."""
    d = dims(cfg)
    per_layer = d["I"] * d["N"] + (d["K"] - 1) * d["conv_dim"]
    return d["mamba_layers"] * per_layer * dtype_bytes


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    state_rows: float, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2, state_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once, each row's cached K/V once and the new token's
    written, and the state of each of the ``state_rows`` rows it
    advances READ once. The state's write is left out: a program that
    fuses several steps may write it once for all of them (this one
    does, ``kvcache.write_state``), and one that writes it every step
    moves more than is counted. Activations, logits and sampling are
    left out too, so a share computed from this is a lower bound on the
    traffic and cannot overstate the roofline. ``dt_bias``, ``A_log``
    and ``D`` are float32 and counted at the weights' width: 14 KB a
    model."""
    d = dims(cfg)
    weights = (_trunk_params(d) + d["H"] * d["V"]) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    state = state_rows * state_bytes_per_sequence(cfg, state_dtype_bytes)
    return float(weights + kv + state)


def forward_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    """Multiply-adds x 2 for one token attending over ``ctx`` cached
    tokens: the layers' and head's matrix multiplications, the K-tap
    convolutions, the state's update and read in the mamba layers
    (2 I N each), and QK^T and PV in the attention layers."""
    d = dims(cfg)
    H, I = d["H"], d["I"]
    matmul = (
        d["mamba_layers"] * (
            H * (I + d["conv_dim"] + d["heads"]) + I * H
            + d["conv_dim"] * d["K"] + 2 * I * d["N"]
        )
        + d["attn_layers"] * (H * d["NH"] * d["Dh"] * 2 + 2 * H * d["KVH"] * d["Dh"])
        + d["L"] * 3 * H * d["F"]
        + H * d["V"]
    )
    attn = 2.0 * d["attn_layers"] * d["NH"] * d["Dh"] * ctx
    return 2.0 * (matmul + attn)
