"""How close the BLOCK form of the paged decode kernel comes to the HBM
bound: the K/V the forwards' rows NEEDED (each row's cached tokens,
``batch`` x ``avg_ctx`` of the ``decode_window`` spans, K and V of
``num_key_value_heads x head_dim`` a token a layer, fetched ONCE for the
block's positions: ``bytes_and_flops_bd.block_kv_bytes``) over the peak
bytes/s, as a share of the device time of the ``paged_decode_attention``
ops in the traced window. Forwards are counted as
``decode_step_device_ms`` counts steps. A page's unused tail and a page
fetched once a position instead of once a block count nothing: they
show as lost share, whatever implements the block's attention.

Where the configuration has no ``block_length``, the trace has no
``paged_decode_attention`` op (the gathered XLA form) or the spans
carry no ``avg_ctx`` there is nothing to read."""

from .. import bytes_and_flops_bd as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "paged_decode_attention"


def read(r):
    if "block_length" not in r.cfg or r.trace is None:
        return None
    seconds = sum(
        s for name, s in (r.trace.get("op_s") or {}).items() if OP in name
    )
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "avg_ctx" in s[3] and "batch" in s[3]]
    if seconds <= 0 or got is None or not spans:
        return None
    steps = [float(s[3].get("steps", 1)) for s in spans]
    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    a_forward = sum(
        counts.block_kv_bytes(
            r.cfg, batch=float(s[3]["batch"]), ctx=float(s[3]["avg_ctx"]),
            kv_dtype_bytes=width,
        ) * w for s, w in zip(spans, steps)
    ) / sum(steps)
    least_s = got[1] * a_forward / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
