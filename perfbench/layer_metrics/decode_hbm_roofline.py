"""How close a decode step comes to the HBM bound: the bytes one step
must move (weights once, each row's cached K/V, the new K/V written:
``bytes_and_flops.decode_bytes_per_step`` at the batch and mean context
the ``decode_window`` spans report) a chip, over the peak bytes/s, as a
share of the measured ``decode_step_device_ms``. Decode is HBM-bound:
its arithmetic intensity is about one multiply-add a byte a row.

Of a routed configuration's experts a step reads only those some row
chose: the spans' ``experts_touched`` (distinct experts a layer a step,
counted by the program). Where a routed configuration's spans do not
carry it there is nothing to read: a guess from uniform routing is the
most a routing can touch and would overstate the share."""

from .. import bytes_and_flops
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    got = steps_and_seconds(r)
    spans = r.spans_in_trace("decode_window")
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    steps = [float(s[3].get("steps", 1)) for s in spans]

    def per_step_mean(key):
        return sum(
            float(s[3].get(key, 0)) * w for s, w in zip(spans, steps)
        ) / sum(steps)

    batch, ctx = per_step_mean("batch"), per_step_mean("avg_ctx")
    touched = None
    if bytes_and_flops.routed(r.cfg) is not None:
        if any("experts_touched" not in s[3] for s in spans):
            return None
        touched = per_step_mean("experts_touched")
    dtype_bytes = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = bytes_and_flops.decode_bytes_per_step(
        r.cfg, batch=batch, mean_ctx=ctx, experts_touched=touched,
        weight_dtype_bytes=dtype_bytes, kv_dtype_bytes=dtype_bytes,
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
