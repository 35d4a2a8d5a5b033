"""Operations and bytes of a model whose residual stream is ``hc_mult``
lanes mixed a token in every sublayer (manifold-constrained
hyper-connections) round latent attention (MLA) and a dense SwiGLU FFN
(the first ``first_k_dense_replace`` layers) or a routed FFN of
``n_routed_experts`` gated experts, every one held, beside one shared
expert; from shapes alone. Kept with the benchmark, beside
``bytes_and_flops_mla.py`` (the same layers on a plain residual, a held
share of the experts), so that no later PR changes the denominator of a
roofline share.

A configuration is the dict of a ``configs/*.json`` file with the
``xing4_0`` keys (DeepSeek-V3's set and ``hc_mult``,
``hc_sinkhorn_iters``). Everything here is a count; nothing is measured.
No width is padded: a cached row is ``kv_lora_rank + qk_rope_head_dim``
= 576 wide whatever tile a kernel would round it to, a head's K is 192
and its V 128 wide, and a prefilled row is counted at its own length,
the causal half of its square. The layers' own counts are
``bytes_and_flops_mla``'s (imported, not copied); what this file adds is
the stream.
"""

from __future__ import annotations

from typing import Any, Dict

from . import bytes_and_flops_mla as mla


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if "share" in cfg:
        raise ValueError("bytes_and_flops_mhc: every expert is held (no share)")
    d = mla.dims(cfg)
    n = int(cfg["hc_mult"])
    if n < 2:
        raise ValueError("bytes_and_flops_mhc: hc_mult under 2 is no stream")
    d.update(n=n, sinkhorn=int(cfg["hc_sinkhorn_iters"]), sublayers=2 * d["L"])
    return d


def hc_params(d) -> int:
    """ONE sublayer's hyper-connection: ``phi`` [n C, n^2 + 2 n], its
    bias and the three alphas."""
    k = d["n"] * (d["n"] + 2)
    return d["n"] * d["H"] * k + k + 3


def param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter the runner holds: ``bytes_and_flops_mla``'s layers,
    final norm, embedding and head, and two hyper-connections a layer."""
    d = dims(cfg)
    return mla.param_count(cfg) + d["sublayers"] * hc_params(d)


def active_params_per_token(cfg: Dict[str, Any]) -> int:
    """Parameters a token's forward multiplies by (the published "A4B"):
    everything but the embedding table, with ``num_experts_per_tok`` of a
    routed layer's experts."""
    d = dims(cfg)
    trunk = (
        d["dense_layers"] * mla.dense_layer_params(d)
        + d["moe_layers"] * mla.routed_layer_params(d, d["top_k"])
        + d["H"]
    )
    return int(trunk + d["sublayers"] * hc_params(d) + d["H"] * d["V"])


def decode_bytes_per_step(
    cfg: Dict[str, Any], *, batch: float, mean_ctx: float,
    experts_touched: float, stream_bytes: float = 0.0,
    weight_dtype_bytes: int = 2, kv_dtype_bytes: int = 2,
) -> float:
    """HBM bytes one decode step over ``batch`` rows must move:
    ``bytes_and_flops_mla``'s (the weights once, of the experts those
    touched; each row's cached latent rows once), the hyper-connections'
    parameters, and ``stream_bytes``, what the rows' residual stream
    must move in the step (the ``decode_window`` spans'
    ``hc_stream_bytes`` a step: a sublayer reads the lanes once and
    writes them once; the program's ``ModelRunner.stream_bytes`` is the
    one definition). Activations other than the stream, logits, the
    router's sort and sampling are left out: a share computed from this
    is a lower bound on the traffic."""
    d = dims(cfg)
    return float(
        mla.decode_bytes_per_step(
            cfg, batch=batch, mean_ctx=mean_ctx,
            experts_touched=experts_touched,
            weight_dtype_bytes=weight_dtype_bytes,
            kv_dtype_bytes=kv_dtype_bytes,
        )
        + d["sublayers"] * hc_params(d) * weight_dtype_bytes
        + stream_bytes
    )


def hc_flops_per_token(cfg: Dict[str, Any]) -> float:
    """Multiply-adds x 2 of ONE token's hyper-connections: the
    projection, the read and the mix a sublayer. The Sinkhorn's
    elementwise passes (n^2 a pass) are left out."""
    d = dims(cfg)
    n, H = d["n"], d["H"]
    return 2.0 * d["sublayers"] * (n * H * n * (n + 2) + n * H + (n * n + n) * H)


def prefill_flops_per_row(cfg: Dict[str, Any], tokens: float) -> float:
    """Multiply-adds x 2 that prefilling ONE row of ``tokens`` tokens
    with no past needs, in the EXPANDED form: ``bytes_and_flops_mla``'s
    (projections, the causal half of the attention's square, the head
    for the one position sampled from) and the hyper-connections'."""
    return mla.prefill_flops_per_row(cfg, tokens) + (
        hc_flops_per_token(cfg) * tokens
    )
