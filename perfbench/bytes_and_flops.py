"""Operations and bytes the algorithm needs, from shapes alone, and the
table of hardware peaks. Kept with the benchmark so that no later PR
changes the denominator of a roofline share.

A configuration is the dict of a ``configs/*.json`` file (the published
``config.json`` keys). Everything here is a count computed from shapes;
nothing is measured.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Optional

_PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind`` (exact ``jax`` string).
    A device that is not in the table is an error, never a default."""
    table = json.loads(_PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; "
            f"perfbench/peaks.json knows {sorted(table)}"
        )
    return dict(table[device_kind])


def _dims(cfg: Dict[str, Any]):
    H = int(cfg["hidden_size"])
    L = int(cfg["num_hidden_layers"])
    NH = int(cfg["num_attention_heads"])
    KVH = int(cfg["num_key_value_heads"])
    Dh = int(cfg.get("head_dim") or H // NH)
    F = int(cfg["intermediate_size"])
    V = int(cfg["vocab_size"])
    return H, L, NH, KVH, Dh, F, V


def routed(cfg: Dict[str, Any]):
    """(experts, experts a token, expert width) of a routed block, or
    None for a dense one: a configuration routes when its published
    keys say so (``num_experts`` > 0)."""
    E = int(cfg.get("num_experts") or 0)
    if E <= 0:
        return None
    return E, int(cfg["num_experts_per_tok"]), int(cfg["moe_intermediate_size"])


def _experts_per_token(cfg: Dict[str, Any]) -> Optional[int]:
    dims = routed(cfg)
    return dims and dims[1]


def _attention_params(cfg: Dict[str, Any]) -> int:
    H, _L, NH, KVH, Dh, _F, _V = _dims(cfg)
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H


def layer_matmul_params(cfg: Dict[str, Any], experts: Optional[float] = None):
    """Weights of one block's matrix multiplications: attention and a
    dense MLP, or attention, the router and ``experts`` experts (all of
    them unless said) of a routed one, whose ``intermediate_size`` key
    is unused."""
    H, _L, _NH, _KVH, _Dh, F, _V = _dims(cfg)
    dims = routed(cfg)
    if dims is None:
        return _attention_params(cfg) + 3 * H * F
    E, _K, Fm = dims
    return _attention_params(cfg) + H * E + (E if experts is None else experts) * 3 * H * Fm


def _model_params(cfg: Dict[str, Any], experts: Optional[float], embedding: bool):
    H, L, _NH, _KVH, Dh, _F, V = _dims(cfg)
    n = L * (layer_matmul_params(cfg, experts) + 2 * H + 2 * Dh) + H + H * V
    if embedding and not cfg.get("tie_word_embeddings", True):
        n += V * H
    return n


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter of the model: blocks (with their two RMSNorm
    vectors and the two QK-norm vectors, and every expert of a routed
    one), embedding, final norm and, when untied, the output head."""
    return _model_params(cfg, None, embedding=True)


def active_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters one token's forward pass uses: ``param_count`` with a
    routed block's ``num_experts_per_tok`` experts for all of them."""
    return _model_params(cfg, _experts_per_token(cfg), embedding=True)


def decode_weight_params(cfg: Dict[str, Any], experts_touched: Optional[float] = None):
    """Parameters one decode step must READ: every block and the output
    head in full; the embedding table is only gathered (a row a
    sequence), so an untied table does not count. Of a routed block's
    experts the step reads those some row of the batch chose:
    ``experts_touched`` a layer (the mean over layers of the distinct
    experts, as the program counted them; all of them unless said)."""
    return _model_params(cfg, experts_touched, embedding=False)


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    _H, L, _NH, KVH, Dh, _F, _V = _dims(cfg)
    return L * 2 * KVH * Dh * kv_dtype_bytes


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    experts_touched: Optional[float] = None,
    weight_dtype_bytes: int = 2, kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move, summed
    over the chips that share the model: the weights once, each row's
    cached K/V once (``mean_ctx`` tokens) and the new token's K/V
    written. Activations, logits and sampling are left out (they are
    two orders smaller), so a share computed from this is a lower
    bound on the traffic and cannot overstate the roofline. A routed
    configuration must say ``experts_touched``: attention, norms,
    router and head are read in full, of the experts only those. No
    default: the most a routing can touch (what uniform routing gives)
    would overstate the share."""
    if routed(cfg) is not None and experts_touched is None:
        raise ValueError(
            "decode_bytes_per_step: a routed configuration needs "
            "experts_touched (distinct experts a layer a step, measured)"
        )
    weights = decode_weight_params(cfg, experts_touched) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    return float(weights + kv)


def forward_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    """Multiply-adds x 2 for one token attending over ``ctx`` cached
    tokens: the blocks' and head's matrix multiplications (of a routed
    block: the router and ``num_experts_per_tok`` experts) plus QK^T
    and PV."""
    H, L, NH, _KVH, Dh, _F, V = _dims(cfg)
    matmul = 2.0 * (L * layer_matmul_params(cfg, _experts_per_token(cfg)) + H * V)
    attn = 2.0 * 2.0 * L * NH * Dh * ctx
    return matmul + attn
