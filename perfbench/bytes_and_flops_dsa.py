"""Operations and bytes of a model whose every layer is latent attention
(MLA) under LEARNED SPARSE attention (an indexer of ``index_n_heads``
heads ``index_head_dim`` wide that keeps ``index_topk`` positions of a
row's past), followed by a dense SwiGLU FFN (the first
``first_k_dense_replace`` layers) or a routed FFN of gated experts beside
one shared expert, of which this chip holds ``n_routed_experts`` of the
router's ``share.experts_published``; from shapes alone. Kept with the
benchmark, beside ``bytes_and_flops_mla.py`` (the same layers with no
indexer, whose counts of the parts the two share this file calls), so
that no later PR changes the denominator of a roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
``glm_moe_dsa`` keys (DeepSeek-V3's set and the four ``index_*``).
Everything here is a count of what the computation NEEDS; nothing is
measured and no width is padded: a cached latent row is
``kv_lora_rank + qk_rope_head_dim`` = 576 wide and an index key
``index_head_dim`` = 128, whatever tile a pool rounds them to; a decode
step reads a row's index keys over its WHOLE context and
``min(context, index_topk)`` latent rows; a prefilled row's indexer
scores the causal half of its square and its attention runs over
``min(t + 1, index_topk)`` keys a query, whatever a masked dense product
computes beyond that (which therefore shows as lost share).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import bytes_and_flops_mla as mla


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = mla.dims(cfg)
    d.update(
        NHi=int(cfg["index_n_heads"]), Di=int(cfg["index_head_dim"]),
        topk=int(cfg["index_topk"]),
    )
    if min(d["NHi"], d["Di"], d["topk"]) < 1:
        raise ValueError("bytes_and_flops_dsa: the index_* keys must be >= 1")
    return d


def indexer_params(d) -> int:
    """One layer's indexer: the index queries' up-projection from the
    query latent, the index key's projection with its LayerNorm's scale
    and bias, a weight a head."""
    return (
        d["Rq"] * d["NHi"] * d["Di"] + d["H"] * d["Di"] + 2 * d["Di"]
        + d["H"] * d["NHi"]
    )


def _trunk_params(d, experts: Optional[float]) -> float:
    return mla._trunk_params(d, experts) + d["L"] * indexer_params(d)


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: the layers by kind (of a routed
    layer the HELD experts), each with its indexer, the final norm, the
    embedding and, when untied, the head. No multi-token-prediction
    block."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["H"] * d["V"]
    return int(_trunk_params(d, None) + d["V"] * d["H"] + head)


def decode_weight_params(cfg: Dict[str, Any], experts_touched: float) -> float:
    """Parameters one decode step must READ: every layer's attention,
    indexer, dense FFN, router, shared expert and norms and the output
    head in full, of each routed layer's held experts the
    ``experts_touched`` some row chose. The embedding is read a row a
    token and left out."""
    d = dims(cfg)
    return _trunk_params(d, experts_touched) + d["H"] * d["V"]


def cache_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a token keeps in the cache: a latent row and an index key,
    every layer."""
    d = dims(cfg)
    return d["L"] * (d["Rkv"] + d["Dr"] + d["Di"]) * dtype_bytes


def sparse_read_bytes_per_row(
    cfg: Dict[str, Any], ctx: float, dtype_bytes: int = 2
) -> float:
    """Cached bytes ONE row's decode step must read at a context of
    ``ctx`` rows: every index key of the context, and the latent rows
    the selection keeps (at most ``index_topk``), every layer."""
    d = dims(cfg)
    return d["L"] * dtype_bytes * (
        ctx * d["Di"] + min(ctx, d["topk"]) * (d["Rkv"] + d["Dr"])
    )


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    experts_touched: float, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move: the
    weights once (of the held experts those touched), each row's index
    keys over its context and its selected latent rows once, and the new
    token's two rows written. At the MEAN context: ``min`` is concave,
    so rows spread about the mean need no more than this counts only
    where they straddle ``index_topk``; the cell's rows are all past it.
    Activations, logits, the scores, the selection and sampling are left
    out, and a gathered copy counts nothing: a share computed from this
    is a lower bound on the traffic and cannot overstate the roofline."""
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    cached = batch * (
        sparse_read_bytes_per_row(cfg, mean_ctx, kv_dtype_bytes)
        + cache_bytes_per_token(cfg, kv_dtype_bytes)
    )
    return float(weights + cached)


def prefill_flops_per_row(cfg: Dict[str, Any], tokens: float) -> float:
    """Multiply-adds x 2 that prefilling ONE row of ``tokens`` tokens
    with no past needs ON THIS CHIP, in the EXPANDED form: a token's
    projections (attention, indexer, the dense FFN or the router, the
    shared expert and ``num_experts_per_tok`` experts times the held
    share), the indexer's scores over the causal half of the square
    (``index_n_heads`` products of ``index_head_dim``), QK^T at a head's
    192 + 64 and PV at its 256 over the ``min(t + 1, index_topk)`` keys
    a query keeps, and the head for the one position sampled from."""
    d = dims(cfg)
    mine = d["top_k"] * d["E_held"] / d["E_router"]
    per_token = (
        d["L"] * (mla.mla_params(d) + indexer_params(d))
        + d["dense_layers"] * 3 * d["H"] * d["F"]
        + d["moe_layers"] * (
            d["H"] * d["E_router"] + 3 * d["H"] * d["Fs"]
            + mine * mla.expert_params(d)
        )
    )
    causal = tokens * (tokens + 1.0) / 2.0          # (query, key) pairs
    short = min(tokens, d["topk"])
    kept = short * (short + 1.0) / 2.0 + (tokens - short) * d["topk"]
    pairs = d["L"] * (
        d["NHi"] * d["Di"] * causal
        + d["NH"] * (d["Dn"] + d["Dr"] + d["Dv"]) * kept
    )
    return 2.0 * (per_token * tokens + pairs + d["H"] * d["V"])
