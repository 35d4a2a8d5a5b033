"""Operations and bytes of a model whose mixers are Kimi Delta Attention
(a gated delta rule: ``linear_attn_config``) in every layer but those of
``gqa_layers``, which are softmax GQA with an output gate, and whose
every FFN routes, from shapes alone. Of a layer's experts this chip
holds ``n_routed_experts`` of the router's ``share.experts_published``.
Kept with the benchmark, beside ``bytes_and_flops_ssm_moe.py`` (Mamba-2
state, two-matrix experts), so that no later PR changes the denominator
of a roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
``solar_open2`` keys. Everything here is a count; nothing is measured.
No width is padded.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    lin = dict(cfg["linear_attn_config"])
    L = int(cfg["num_hidden_layers"])
    gqa = [int(i) for i in cfg["gqa_layers"]]
    if any(not 0 <= i < L for i in gqa):
        raise ValueError("bytes_and_flops_kda: gqa_layers outside the depth")
    heads, dk = int(lin["num_heads"]), int(lin["head_dim"])
    held = int(cfg["n_routed_experts"])
    return {
        "H": int(cfg["hidden_size"]), "L": L, "V": int(cfg["vocab_size"]),
        "NH": int(cfg["num_attention_heads"]),
        "KVH": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "heads": heads, "dk": dk, "I": heads * dk,
        "K": int(lin["short_conv_kernel_size"]),
        # the decay's and the gate's pairs: the head's width unless said
        "R": int(cfg.get("kda_rank", dk)),
        "E_held": held,
        "E_router": int((cfg.get("share") or {}).get("experts_published", held)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg.get("n_shared_experts", 1)),
        "attn_layers": len(gqa), "kda_layers": L - len(gqa),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def kda_mixer_params(d) -> int:
    """q, k, v projections, the 3 convs' taps, the decay's pair with
    dt_bias a channel and A_log a head, beta, the gate's pair with its
    bias, the head norm, the out projection."""
    H, I, R = d["H"], d["I"], d["R"]
    return (
        3 * H * I + 3 * I * d["K"] + (H * R + R * I + I + d["heads"])
        + H * d["heads"] + (H * R + R * I + I) + d["dk"] + I * H
    )


def attention_mixer_params(d) -> int:
    """q, k, v, the output gate a channel and the out projection."""
    H, NH, KVH, Dh = d["H"], d["NH"], d["KVH"], d["Dh"]
    return H * NH * Dh + 2 * H * KVH * Dh + 2 * NH * Dh * H


def expert_params(d) -> int:
    """One routed expert: three matrices (SwiGLU)."""
    return 3 * d["H"] * d["Fm"]


def ffn_params(d, experts: Optional[float] = None) -> float:
    """A layer outside its mixer: the router over every published expert
    with its selection bias, ``experts`` experts (the held ones unless
    said), the shared expert(s) of an expert's width, the two norms."""
    E = d["E_held"] if experts is None else experts
    return (
        d["H"] * d["E_router"] + d["E_router"]
        + (E + d["shared"]) * expert_params(d) + 2 * d["H"]
    )


def _trunk_params(d, experts: Optional[float]) -> float:
    return (
        d["kda_layers"] * kda_mixer_params(d)
        + d["attn_layers"] * attention_mixer_params(d)
        + d["L"] * ffn_params(d, experts) + d["H"]          # final norm
    )


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers (of each the HELD
    experts), the final norm, the embedding and the untied head over the
    held slice of the vocabulary."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d, None) + d["V"] * d["H"] + head)


def _published(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at the published depth, experts and vocabulary
    (the file's ``published``), every expert held."""
    cut = ("num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size")
    return dict(cfg, share={}, **{k: cfg["published"][k] for k in cut})


def published_param_count(cfg: Dict[str, Any]) -> int:
    """The same count at the published size: the model's."""
    return param_count(_published(cfg))


def active_param_count(cfg: Dict[str, Any], published: bool = False) -> int:
    """Parameters one token's forward pass uses: of a layer's experts
    ``num_experts_per_tok`` (times the held share of the router's ON THIS
    CHIP; all of them at the ``published`` size), with embedding and
    head as the published count has them."""
    if published:
        d = dims(_published(cfg))
        return int(_trunk_params(d, d["top_k"]) + 2 * d["V"] * d["H"])
    d = dims(cfg)
    mine = d["top_k"] * d["E_held"] / d["E_router"]
    return int(_trunk_params(d, mine) + d["V"] * d["H"])


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every layer's mixer,
    router, shared expert and norms and the output head in full, of each
    layer's held experts the ``experts_touched`` some row chose. The
    embedding is read a row a token and left out."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over the GQA layers."""
    d = dims(cfg)
    return d["attn_layers"] * 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def state_matrix_bytes_per_sequence(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """The delta-rule state a sequence keeps over the KDA layers: a
    matrix [dk, dk] a head."""
    d = dims(cfg)
    return d["kda_layers"] * d["heads"] * d["dk"] * d["dk"] * dtype_bytes


def state_bytes_per_sequence(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """The matrices and the convs' K-1 columns of [q | k | v]."""
    d = dims(cfg)
    conv = d["kda_layers"] * (d["K"] - 1) * 3 * d["I"] * dtype_bytes
    return state_matrix_bytes_per_sequence(cfg, dtype_bytes) + conv


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    state_rows: float, experts_touched: float, steps_per_commit: float,
    weight_dtype_bytes: int = 2, kv_dtype_bytes: int = 2,
    state_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once (of the held experts those touched), each row's cached
    K/V once and the new token's written, and the delta-rule state of
    each of the ``state_rows`` rows it advances READ once a step and
    WRITTEN once a commit, which serves ``steps_per_commit`` steps (a
    fused window's; a write a step would overstate the need of a program
    that commits a window at a time). Activations, logits, the conv
    columns, the router's sort and sampling are left out: a share
    computed from this is a lower bound on the traffic and cannot
    overstate the roofline."""
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    state = state_rows * state_matrix_bytes_per_sequence(
        cfg, state_dtype_bytes
    ) * (1.0 + 1.0 / max(float(steps_per_commit), 1.0))
    return float(weights + kv + state)


def prefill_flops_per_row(cfg: Dict[str, Any], tokens: float) -> float:
    """Multiply-adds x 2 a prompt of ``tokens`` needs ON THIS CHIP: the
    layers' and head's matrix multiplications a token (of a layer the
    router, the shared expert and ``num_experts_per_tok`` experts times
    the held share; the head once, at the last position), the K-tap
    convolutions, the delta rule's state products a token (the decay,
    ``k^T S``, the rank-one update and ``S^T q``: 4 dk dk a head: the
    recurrence's own count, which a chunk form that adds a triangular
    solve and pairwise decays does not lower) and the causal half of
    QK^T and PV in the GQA layers."""
    d = dims(cfg)
    H, I, R = d["H"], d["I"], d["R"]
    mine = d["top_k"] * d["E_held"] / d["E_router"]
    per_token = (
        d["kda_layers"] * (
            3 * H * I + 3 * I * d["K"] + 2 * (H * R + R * I) + H * d["heads"]
            + I * H + 4 * d["heads"] * d["dk"] * d["dk"]
        )
        + d["attn_layers"] * (
            H * d["NH"] * d["Dh"] * 3 + 2 * H * d["KVH"] * d["Dh"]
        )
        + d["L"] * (
            H * d["E_router"] + (d["shared"] + mine) * expert_params(d)
        )
    )
    attn = d["attn_layers"] * d["NH"] * d["Dh"] * tokens * (tokens + 1.0)
    return 2.0 * (tokens * per_token + attn + H * d["V"])
