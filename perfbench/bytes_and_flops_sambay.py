"""Operations and bytes of a decoder-hybrid-decoder with differential
attention, from shapes alone (``model_type`` ``phi4flash``): by the
layer's place (``layout``) a mixer is Mamba-1, differential attention
over a window, differential attention over everything (ONE layer, whose
K/V the cross layers read again), a gated memory unit or a differential
cross layer; every block has a dense SwiGLU under LayerNorms with a
bias, and the head is the tied embedding. Kept with the benchmark, so
that no later PR changes the denominator of a roofline share
(``SAMBAY_LAYERS.md`` says how each is counted).

A configuration is the dict of a ``configs/*.json`` file with the
``phi4flash`` keys (and ``mamba_*`` for the sizes the published file has
no key for). Everything here is a count; nothing is measured.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

KINDS = ("mamba1", "swa", "attn", "gmu", "cross")


def layout(layers: int, mb_per_layer: int) -> Sequence[str]:
    """Each layer's mixer, by the published rule (``reference/
    sambay_diff.md``)."""
    half = layers // 2
    return tuple(
        ("mamba1" if l % mb_per_layer == 0 else "swa") if l <= half
        else "attn" if l == half + 1
        else ("gmu" if l % mb_per_layer == 0 else "cross")
        for l in range(layers)
    )


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    H, NH = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    L = int(cfg["num_hidden_layers"])
    kinds = layout(L, int(cfg["mb_per_layer"]))
    d = {
        "H": H, "L": L, "NH": NH, "KVH": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or H // NH),
        "V": int(cfg["vocab_size"]), "F": int(cfg["intermediate_size"]),
        "window": int(cfg["sliding_window"]),
        "I": int(cfg.get("mamba_expand", 2)) * H,
        "N": int(cfg.get("mamba_d_state", 16)),
        "K": int(cfg.get("mamba_d_conv", 4)),
        "R": int(cfg.get("mamba_dt_rank") or math.ceil(H / 16)),
    }
    d.update({k: kinds.count(k) for k in KINDS})
    return d


def _norm(d) -> int:
    return 2 * d["H"]                       # a LayerNorm's scale and bias


def mamba1_params(d) -> int:
    H, I, N, K, R = d["H"], d["I"], d["N"], d["K"], d["R"]
    return (
        H * 2 * I + I * K + I + I * (R + 2 * N) + R * I + I + I * N + I
        + I * H + _norm(d)
    )


def attention_params(d, own_kv: bool) -> int:
    """q and out with their biases, the four lambda vectors and the
    inner norm; k and v with theirs where the layer keeps K/V."""
    H, NHD, KVD, Dh = d["H"], d["NH"] * d["Dh"], d["KVH"] * d["Dh"], d["Dh"]
    n = H * NHD + NHD + NHD * H + H + 4 * Dh + 2 * Dh + _norm(d)
    return n + (2 * (H * KVD + KVD) if own_kv else 0)


def memory_unit_params(d) -> int:
    return 2 * d["H"] * d["I"] + _norm(d)


def mlp_params(d) -> int:
    return 3 * d["H"] * d["F"] + _norm(d)


def params_by_kind(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = dims(cfg)
    return {
        "embedding": d["V"] * d["H"],
        "final_norm": _norm(d),
        "mlp": d["L"] * mlp_params(d),
        "mamba1": d["mamba1"] * mamba1_params(d),
        "attention_with_kv": (d["swa"] + d["attn"]) * attention_params(d, True),
        "cross": d["cross"] * attention_params(d, False),
        "memory_unit": d["gmu"] * memory_unit_params(d),
    }


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds (the head is the embedding)."""
    return int(sum(params_by_kind(cfg).values()))


def kv_bytes_per_token_layer(cfg: Dict[str, Any], kv_dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE layer's pool."""
    d = dims(cfg)
    return 2 * d["KVH"] * d["Dh"] * kv_dtype_bytes


def decode_kv_bytes(
    cfg: Dict[str, Any], *, batch: float, kv_tokens_full: float,
    kv_tokens_window: float, kv_readers_full: Optional[float] = None,
    kv_readers_window: Optional[float] = None, kv_dtype_bytes: int = 2,
    written: float = 1.0,
) -> float:
    """K/V one decode step over ``batch`` rows must move: ``batch x
    kv_tokens x 5,120 B x readers`` a pool, EACH reader counted (a cross
    layer reads the full layer's pages again: nothing on the chip keeps
    8.6 MB a row between two layers), and the new token's ``written``
    once a layer that keeps K/V (0: the reads alone)."""
    d = dims(cfg)
    rf = d["attn"] + d["cross"] if kv_readers_full is None else kv_readers_full
    rw = d["swa"] if kv_readers_window is None else kv_readers_window
    return float(batch * kv_bytes_per_token_layer(cfg, kv_dtype_bytes) * (
        rf * kv_tokens_full + rw * kv_tokens_window
        + written * (d["attn"] + d["swa"])
    ))


def state_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, state_layers: Optional[float] = None,
    state_dtype_bytes: int = 2,
) -> float:
    """A slot's matrices ``[N, I]`` and its K-1 conv columns of every
    state layer, for ``batch`` rows, READ AND WRITTEN once a step (the
    recurrence advances every row's state every token, wherever the
    program keeps it between commits)."""
    d = dims(cfg)
    layers = d["mamba1"] if state_layers is None else state_layers
    a_slot = (d["N"] + d["K"] - 1) * d["I"] * state_dtype_bytes
    return float(2 * batch * layers * a_slot)


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, kv_tokens_full: float,
    kv_tokens_window: float, kv_readers_full: Optional[float] = None,
    kv_readers_window: Optional[float] = None,
    state_layers: Optional[float] = None, weight_dtype_bytes: int = 2,
    kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step must move: every weight once (the tied
    embedding once, as the head; its rows as an embedding are a row a
    token and left out), the K/V by pool and reader
    (``decode_kv_bytes``), the slots read and written
    (``state_bytes_per_step``). Activations, logits and sampling are
    left out, so a share computed from this is a lower bound on the
    traffic and cannot overstate the roofline."""
    return float(
        param_count(cfg) * weight_dtype_bytes
        + decode_kv_bytes(
            cfg, batch=batch, kv_tokens_full=kv_tokens_full,
            kv_tokens_window=kv_tokens_window,
            kv_readers_full=kv_readers_full,
            kv_readers_window=kv_readers_window,
            kv_dtype_bytes=kv_dtype_bytes,
        )
        + state_bytes_per_step(
            cfg, batch=batch, state_layers=state_layers,
            state_dtype_bytes=kv_dtype_bytes,
        )
    )


def causal_pairs(n: float, window: Optional[int] = None) -> float:
    """(query, key) pairs of a row of ``n`` tokens under the causal
    mask, a query seeing at most ``window`` keys."""
    if window is None or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * window


def prefill_flops_per_row(cfg: Dict[str, Any], n: float) -> float:
    """Multiply-adds x 2 a prefilled row of ``n`` REAL tokens needs, as
    the MODEL needs them: every position's projections in every layer
    (this program runs the layers behind the shared K/V at every
    position), the MLPs, the Mamba-1 recurrence (``3 N I`` a token: the
    decay, the input and the readout of a state element), the
    differential attention's products as two softmaxes of ``Dh`` keys
    over a value of ``2 Dh`` a differential head (QK^T ``2 x Dh``, PV
    ``2 x 2 Dh`` a pair of positions; NOT the zero-padded 128-wide
    products of the pair form), and the head on ONE position."""
    d = dims(cfg)
    H, I, N, R = d["H"], d["I"], d["N"], d["R"]
    NHD, KVD, Dh = d["NH"] * d["Dh"], d["KVH"] * d["Dh"], d["Dh"]
    per_token = (
        d["L"] * 3 * H * d["F"]
        + d["mamba1"] * (
            H * 2 * I + I * d["K"] + I * (R + 2 * N) + R * I + 3 * N * I
            + I * H
        )
        + (d["swa"] + d["attn"]) * (2 * H * NHD + 2 * H * KVD)
        + d["cross"] * 2 * H * NHD
        + d["gmu"] * 2 * H * I
    )
    heads = d["NH"] // 2                    # differential heads
    a_pair = 2 * Dh + 2 * 2 * Dh            # both softmaxes' QK^T and PV
    attention = heads * a_pair * (
        (d["attn"] + d["cross"]) * causal_pairs(n)
        + d["swa"] * causal_pairs(n, d["window"])
    )
    return 2.0 * (n * per_token + attention + H * d["V"])


def prefill_flops(cfg: Dict[str, Any], rows: Sequence[float]) -> float:
    """A dispatch's: each row at its own length."""
    return float(sum(prefill_flops_per_row(cfg, float(n)) for n in rows if n > 0))
