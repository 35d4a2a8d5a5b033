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
from typing import Any, Dict

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


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights of one dense block's matrix multiplications."""
    H, _L, NH, KVH, Dh, F, _V = _dims(cfg)
    return H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + 3 * H * F


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter of the dense Qwen3 model: blocks (with their two
    RMSNorm vectors and the two QK-norm vectors), embedding, final norm
    and, when untied, the output head."""
    H, L, _NH, _KVH, Dh, _F, V = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * H + 2 * Dh
    n = L * per_layer + V * H + H
    if not cfg.get("tie_word_embeddings", True):
        n += H * V
    return n


def decode_weight_params(cfg: Dict[str, Any]) -> int:
    """Parameters one decode step must READ: every block and the output
    head in full; the embedding table is only gathered (a row a
    sequence), so an untied table does not count."""
    H, L, _NH, _KVH, Dh, _F, V = _dims(cfg)
    return L * (layer_matmul_params(cfg) + 2 * H + 2 * Dh) + H + H * V


def kv_bytes_per_token(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    _H, L, _NH, KVH, Dh, _F, _V = _dims(cfg)
    return L * 2 * KVH * Dh * kv_dtype_bytes


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    weight_dtype_bytes: int = 2, kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move, summed
    over the chips that share the model: the weights once, each row's
    cached K/V once (``mean_ctx`` tokens) and the new token's K/V
    written. Activations, logits and sampling are left out (they are
    two orders smaller), so a share computed from this is a lower
    bound on the traffic and cannot overstate the roofline."""
    weights = decode_weight_params(cfg) * weight_dtype_bytes
    kv = batch * kv_bytes_per_token(cfg, kv_dtype_bytes) * (mean_ctx + 1.0)
    return float(weights + kv)


def forward_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    """Multiply-adds x 2 for one token attending over ``ctx`` cached
    tokens: the blocks' and head's matrix multiplications plus QK^T and
    PV."""
    H, L, NH, _KVH, Dh, _F, V = _dims(cfg)
    matmul = 2.0 * (L * layer_matmul_params(cfg) + H * V)
    attn = 2.0 * 2.0 * L * NH * Dh * ctx
    return matmul + attn
