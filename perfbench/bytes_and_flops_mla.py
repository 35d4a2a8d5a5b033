"""Operations and bytes of a model whose every layer is latent attention
(MLA) followed by a dense SwiGLU FFN (the first ``first_k_dense_replace``
layers) or a routed FFN of gated experts beside one shared expert, of
which this chip holds ``n_routed_experts`` of the router's
``share.experts_published``; from shapes alone. Kept with the benchmark,
beside ``bytes_and_flops_hybrid.py`` (GQA pages, every expert held) and
``bytes_and_flops_ssm_moe.py`` (one-sublayer blocks, two-matrix experts),
so that no later PR changes the denominator of a roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
``joyai_llm_flash`` keys (DeepSeek-V3's set). Everything here is a
count; nothing is measured. No width is padded: a cached row is
``kv_lora_rank + qk_rope_head_dim`` = 576 wide whatever tile a kernel
would round it to, a head's K is 192 and its V 128 wide, and a prefilled
row is counted at its own length, the causal half of its square.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    L, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    if not 0 <= dense <= L or int(cfg.get("moe_layer_freq", 1)) != 1:
        raise ValueError(
            "bytes_and_flops_mla: first_k_dense_replace outside the layers, "
            "or moe_layer_freq other than 1"
        )
    held = int(cfg["n_routed_experts"])
    return {
        "H": int(cfg["hidden_size"]), "L": L, "V": int(cfg["vocab_size"]),
        "NH": int(cfg["num_attention_heads"]),
        "Rq": int(cfg["q_lora_rank"]), "Rkv": int(cfg["kv_lora_rank"]),
        "Dn": int(cfg["qk_nope_head_dim"]), "Dr": int(cfg["qk_rope_head_dim"]),
        "Dv": int(cfg["v_head_dim"]),
        "F": int(cfg["intermediate_size"]),
        "Fm": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_intermediate_size"])
        * int(cfg.get("n_shared_experts", 1)),
        "E_held": held,
        "E_router": int((cfg.get("share") or {}).get("experts_published", held)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "dense_layers": dense, "moe_layers": L - dense,
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def mla_params(d) -> int:
    """One layer's attention: the query's down-projection, its norm and
    up-projection, the latent down-projection (values and the shared
    rotary key), its norm, the up-projection to a head's K and V, the
    output projection."""
    H, NH, Rq, Rkv = d["H"], d["NH"], d["Rq"], d["Rkv"]
    return (
        H * Rq + Rq + Rq * NH * (d["Dn"] + d["Dr"])
        + H * (Rkv + d["Dr"]) + Rkv + Rkv * NH * (d["Dn"] + d["Dv"])
        + NH * d["Dv"] * H
    )


def expert_params(d) -> int:
    """One routed expert: three matrices."""
    return 3 * d["H"] * d["Fm"]


def dense_layer_params(d) -> int:
    """Attention, the dense SwiGLU and the layer's two norms."""
    return mla_params(d) + 3 * d["H"] * d["F"] + 2 * d["H"]


def routed_layer_params(d, experts: Optional[float] = None) -> float:
    """Attention, the router over every published expert with its
    selection bias, ``experts`` experts (the held ones unless said), the
    shared expert and the layer's two norms."""
    E = d["E_held"] if experts is None else experts
    return (
        mla_params(d) + d["H"] * d["E_router"] + d["E_router"]
        + E * expert_params(d) + 3 * d["H"] * d["Fs"] + 2 * d["H"]
    )


def _trunk_params(d, experts: Optional[float]) -> float:
    return (
        d["dense_layers"] * dense_layer_params(d)
        + d["moe_layers"] * routed_layer_params(d, experts)
        + d["H"]                                    # final norm
    )


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers by kind (of a routed
    layer the HELD experts), the final norm, the embedding and, when
    untied, the head. No multi-token-prediction block."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d, None) + d["V"] * d["H"] + head)


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every layer's attention,
    dense FFN, router, shared expert and norms and the output head in
    full, of each routed layer's held experts the ``experts_touched``
    some row chose. The embedding is read a row a token and left out."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def latent_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a token keeps in the cache: one row of latent values and the
    shared rotary key, every layer."""
    d = dims(cfg)
    return d["L"] * (d["Rkv"] + d["Dr"]) * dtype_bytes


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    experts_touched: float, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once (of the held experts those touched), and each row's
    cached latent rows once and the new token's written. Activations,
    logits, the router's sort and sampling are left out, and a gathered
    copy of the pages counts nothing: a share computed from this is a
    lower bound on the traffic and cannot overstate the roofline."""
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    latent = batch * latent_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    return float(weights + latent)


def prefill_flops_per_row(cfg: Dict[str, Any], tokens: float) -> float:
    """Multiply-adds x 2 that prefilling ONE row of ``tokens`` tokens
    with no past needs ON THIS CHIP, in the EXPANDED form: a token's
    projections (attention, the dense FFN or the router, the shared
    expert and ``num_experts_per_tok`` experts times the held share),
    the causal half of QK^T at a head's 192 and of PV at its 128, and
    the head for the one position that is sampled from."""
    d = dims(cfg)
    mine = d["top_k"] * d["E_held"] / d["E_router"]
    per_token = (
        d["L"] * mla_params(d)
        + d["dense_layers"] * 3 * d["H"] * d["F"]
        + d["moe_layers"] * (
            d["H"] * d["E_router"] + 3 * d["H"] * d["Fs"]
            + mine * expert_params(d)
        )
    )
    attended = tokens * (tokens + 1.0) / 2.0        # (query, key) pairs
    attn = d["L"] * d["NH"] * (d["Dn"] + d["Dr"] + d["Dv"]) * attended
    return 2.0 * (per_token * tokens + attn + d["H"] * d["V"])
