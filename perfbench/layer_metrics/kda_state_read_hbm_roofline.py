"""How close the delta-rule layers' read of their committed state comes
to the HBM bound: the bytes the traced window's decode steps had to
stream (``kda_state_bytes`` of the ``decode_window`` spans, the rows'
matrices over every KDA layer, once a step; steps counted as
``decode_step_device_ms`` counts them) over the peak bytes/s, as a share
of the device time of the ``kda_state_read`` ops in the same window.
The products' small operands and results are left out, and a window cut
by the trace's edge counts its steps and not all its ops' time, so the
share is a lower bound.

Where the configuration has no ``linear_attn_config``, the trace has no
``kda_state_read`` op (the XLA expression ran) or the spans carry no
``kda_state_bytes`` there is nothing to read."""

from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "kda_state_read"


def per_step_state_bytes(r):
    """The spans' mean ``kda_state_bytes``, a step a weight; None
    without such spans."""
    spans = [s for s in r.spans_in_trace("decode_window")
             if "kda_state_bytes" in s[3]]
    steps = [float(s[3].get("steps", 1)) for s in spans]
    if not spans or sum(steps) <= 0:
        return None
    return sum(
        float(s[3]["kda_state_bytes"]) * w for s, w in zip(spans, steps)
    ) / sum(steps)


def op_seconds(r, op):
    return sum(
        s for name, s in (r.trace.get("op_s") or {}).items() if op in name
    )


def read(r):
    if "linear_attn_config" not in r.cfg or r.trace is None:
        return None
    seconds = op_seconds(r, OP)
    got = steps_and_seconds(r)
    per_step = per_step_state_bytes(r)
    if seconds <= 0 or got is None or per_step is None:
        return None
    least_s = got[1] * per_step / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
