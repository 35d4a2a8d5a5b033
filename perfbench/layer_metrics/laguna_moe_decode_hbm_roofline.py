"""How close a decode step of a model whose layer kinds differ in their
query heads comes to the HBM bound, the WHOLE step: the bytes one step
must move (``bytes_and_flops_laguna.decode_bytes_per_step``: each kind's
mixers at its own heads with their gates, the dense FFN, every routed
layer's router and gated shared expert, of the HELD experts those the
step's rows chose, the head's slice, K/V over the tokens a full layer
and a window layer read) at the ``batch``, ``kv_tokens_full``,
``kv_tokens_window`` and ``experts_touched`` the ``decode_window`` spans
report, over the peak bytes/s, as a share of the measured
``decode_step_device_ms``.

Where the configuration has no ``num_attention_heads_per_layer`` or the
spans carry no ``kv_tokens_window`` / ``experts_touched`` (a program
without the family) there is nothing to read."""

from .. import bytes_and_flops_laguna as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
KEYS = ("batch", "kv_tokens_full", "kv_tokens_window", "experts_touched")


def window_spans(r):
    """The traced window's ``decode_window`` spans that say what a step
    read, each with its steps: ``(spans, steps)``."""
    spans = [s for s in r.spans_in_trace("decode_window")
             if all(k in s[3] for k in KEYS)]
    return spans, [float(s[3].get("steps", 1)) for s in spans]


def per_step_mean(spans, steps, key):
    return sum(
        float(s[3].get(key, 0)) * w for s, w in zip(spans, steps)
    ) / sum(steps)


def read(r):
    if "num_attention_heads_per_layer" not in r.cfg:
        return None
    got = steps_and_seconds(r)
    spans, steps = window_spans(r)
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = counts.decode_bytes_per_step(
        r.cfg, weight_dtype_bytes=width, kv_dtype_bytes=width,
        **{k: per_step_mean(spans, steps, k) for k in KEYS},
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
