"""How close the paged decode kernel comes to the HBM bound under the
PAIR form of differential attention (40 queries of 128 over 10 KV pairs)
over two pools, one of which eight layers read: the K/V both pools'
readers needed (``bytes_and_flops_sambay.decode_kv_bytes`` with nothing
written: ``batch`` rows x the cached tokens a reader of the full pool
and of the window pool reads x the READERS of each, all from the
``decode_window`` spans, K and V of ``num_key_value_heads x head_dim`` a
token a reader) over the peak bytes/s, as a share of the device time of
the ``paged_decode_attention`` ops in the traced window. Steps are
counted as ``decode_step_device_ms`` counts them. A page's unused tail
and a window layer's page of misalignment count nothing: they show as
lost share.

Where the configuration is no ``phi4flash``, the trace has no
``paged_decode_attention`` op (the gathered XLA form) or the spans carry
no ``kv_readers_full`` there is nothing to read."""

from .. import bytes_and_flops_sambay as counts
from .decode_step_device_ms import steps_and_seconds
from .sambay_decode_hbm_roofline import (
    is_family, per_step_mean, width, window_spans,
)

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "paged_decode_attention"
KEYS = ("batch", "kv_tokens_full", "kv_tokens_window", "kv_readers_full",
        "kv_readers_window")


def read(r):
    if not is_family(r) or r.trace is None:
        return None
    seconds = sum(
        s for name, s in (r.trace.get("op_s") or {}).items() if OP in name
    )
    got = steps_and_seconds(r)
    spans, steps = window_spans(r)
    if seconds <= 0 or got is None or not spans:
        return None
    a_step = counts.decode_kv_bytes(
        r.cfg, kv_dtype_bytes=width(r), written=0.0,
        **{k: per_step_mean(spans, steps, k) for k in KEYS},
    )
    least_s = got[1] * a_step / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
