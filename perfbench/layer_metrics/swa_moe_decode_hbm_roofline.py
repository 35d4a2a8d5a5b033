"""How close a decode step of a model with window and full attention
layers comes to the HBM bound: the bytes one step must move
(``bytes_and_flops_swa.decode_bytes_per_step``: the mixers', routers'
and norms' weights and the head, of the experts those the step's rows
chose, K/V over the tokens a full layer and a window layer read) at the
batch, ``kv_tokens_full``, ``kv_tokens_window`` and ``experts_touched``
the ``decode_window`` spans report, over the peak bytes/s, as a share of
the measured ``decode_step_device_ms``. It is the paged decode kernel's
(a window layer fetches its window) and the grouped product's share of
their roofline.

Where the configuration has no ``sliding_window`` or the spans carry no
``kv_tokens_window`` (a program that keeps one pool) there is nothing to
read."""

from .. import bytes_and_flops_swa as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "sliding_window" not in r.cfg or "layer_types" not in r.cfg:
        return None
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "kv_tokens_window" in s[3] and "experts_touched" in s[3]]
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
        r.cfg, batch=per_step_mean("batch"),
        kv_tokens_full=per_step_mean("kv_tokens_full"),
        kv_tokens_window=per_step_mean("kv_tokens_window"),
        experts_touched=per_step_mean("experts_touched"),
        weight_dtype_bytes=dtype_bytes, kv_dtype_bytes=dtype_bytes,
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
