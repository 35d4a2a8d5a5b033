"""How close a decode step of a decoder-hybrid-decoder comes to the HBM
bound, the WHOLE step: the bytes one step must move
(``bytes_and_flops_sambay.decode_bytes_per_step``: every weight once
with the tied embedding once as the head, the K/V of each pool times the
layers that READ it, the state layers' slots read and written, the new
token's K/V written) at the ``batch``, ``kv_tokens_full``,
``kv_tokens_window``, ``kv_readers_full``, ``kv_readers_window`` and
``state_layers`` the ``decode_window`` spans report, over the peak
bytes/s, as a share of the measured ``decode_step_device_ms``.

Where the configuration is no ``phi4flash`` or the spans carry no
``kv_readers_full`` (a program without the family) there is nothing to
read."""

from .. import bytes_and_flops_sambay as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
KEYS = ("batch", "kv_tokens_full", "kv_tokens_window", "kv_readers_full",
        "kv_readers_window", "state_layers")


def is_family(r) -> bool:
    return r.cfg.get("model_type") == "phi4flash"


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


def width(r) -> int:
    return 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4


def read(r):
    if not is_family(r):
        return None
    got = steps_and_seconds(r)
    spans, steps = window_spans(r)
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    total = counts.decode_bytes_per_step(
        r.cfg, weight_dtype_bytes=width(r), kv_dtype_bytes=width(r),
        **{k: per_step_mean(spans, steps, k) for k in KEYS},
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
