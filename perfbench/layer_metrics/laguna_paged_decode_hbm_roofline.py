"""How close the paged decode kernel comes to the HBM bound at 9 and at
6 query heads a KV head in one program: the K/V BOTH kinds' rows needed
(``bytes_and_flops_laguna.decode_kv_bytes`` with nothing written:
``batch`` rows x the cached tokens a full layer and a window layer read,
``kv_tokens_full`` and ``kv_tokens_window`` of the ``decode_window``
spans, K and V of ``num_key_value_heads x head_dim`` a token a layer)
over the peak bytes/s, as a share of the device time of the
``paged_decode_attention`` ops in the traced window. Steps are counted
as ``decode_step_device_ms`` counts them. A page's unused tail and a
window layer's page of misalignment count nothing: they show as lost
share.

Where the configuration has no ``num_attention_heads_per_layer``, the
trace has no ``paged_decode_attention`` op (the gathered XLA form) or
the spans carry no ``kv_tokens_window`` there is nothing to read."""

from .. import bytes_and_flops_laguna as counts
from .decode_step_device_ms import steps_and_seconds
from .laguna_moe_decode_hbm_roofline import per_step_mean, window_spans

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "paged_decode_attention"


def read(r):
    if "num_attention_heads_per_layer" not in r.cfg or r.trace is None:
        return None
    seconds = sum(
        s for name, s in (r.trace.get("op_s") or {}).items() if OP in name
    )
    got = steps_and_seconds(r)
    spans, steps = window_spans(r)
    if seconds <= 0 or got is None or not spans:
        return None
    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    a_step = counts.decode_kv_bytes(
        r.cfg, kv_dtype_bytes=width, written=0.0,
        **{k: per_step_mean(spans, steps, k)
           for k in ("batch", "kv_tokens_full", "kv_tokens_window")},
    )
    least_s = got[1] * a_step / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
