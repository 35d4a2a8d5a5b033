"""How close a decode step of a model with layers of several kinds comes
to the HBM bound: the bytes one step must move
(``bytes_and_flops_hybrid.decode_bytes_per_step``: mixers by
``layer_types``, dense and routed FFNs by ``num_dense_layers``, of the
experts those the step's rows chose, K/V over the attention layers, the
conv state read and written) at the batch, mean context and
``experts_touched`` the ``decode_window`` spans report, over the peak
bytes/s, as a share of the measured ``decode_step_device_ms``.

Where the configuration has no ``layer_types`` or the spans carry no
``experts_touched`` there is nothing to read."""

from .. import bytes_and_flops_hybrid as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "layer_types" not in r.cfg:
        return None
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "experts_touched" in s[3]]
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    steps = [float(s[3].get("steps", 1)) for s in spans]

    def per_step_mean(key):
        return sum(
            float(s[3].get(key, 0)) * w for s, w in zip(spans, steps)
        ) / sum(steps)

    dtype_bytes = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = counts.decode_bytes_per_step(
        r.cfg, batch=per_step_mean("batch"), mean_ctx=per_step_mean("avg_ctx"),
        experts_touched=per_step_mean("experts_touched"),
        weight_dtype_bytes=dtype_bytes, kv_dtype_bytes=dtype_bytes,
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
