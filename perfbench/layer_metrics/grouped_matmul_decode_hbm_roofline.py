"""How close the routed experts' grouped product comes to the HBM bound
in a model of one-sublayer blocks: the bytes of the held experts some
row chose (``experts_touched`` of each routed block, two matrices an
expert, ``bytes_and_flops_ssm_moe.expert_params``), a decode step and a
prefill at a time, over the peak bytes/s, as a share of the device time
of the ``grouped_matmul`` ops in the traced window. Steps and prefills
are counted as ``decode_step_device_ms`` counts them (runs of the decode
and prefill programs in the trace, times the spans' mean ``steps``), the
experts touched are the spans' means. Both sides cover every grouped
product in the window, the prefills' too (a few in a hundred in a
decode-heavy cell). An expert whose rows lie in two row tiles is fetched
twice and counted once, so the share is a lower bound.

Where the configuration has no ``hybrid_override_pattern``, the trace
has no ``grouped_matmul`` op (the products on ``ragged_dot``) or the
spans carry no ``experts_touched`` there is nothing to read."""

from .. import bytes_and_flops_ssm_moe as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "grouped_matmul"
PREFILL = r"prefill"


def _mean(spans, key, weights=None):
    have = [(float(s[3][key]), w) for s, w in zip(spans, weights or [1.0] * len(spans))
            if key in s[3]]
    total = sum(w for _, w in have)
    return None if total <= 0 else sum(v * w for v, w in have) / total


def read(r):
    from ..trace_reduce import module_seconds

    if "hybrid_override_pattern" not in r.cfg or r.trace is None:
        return None
    seconds = sum(
        s for name, s in (r.trace.get("op_s") or {}).items() if OP in name
    )
    got = steps_and_seconds(r)
    windows = r.spans_in_trace("decode_window")
    steps = [float(s[3].get("steps", 1)) for s in windows]
    per_step = _mean(windows, "experts_touched", steps)
    if seconds <= 0 or got is None or per_step is None:
        return None
    touched = got[1] * per_step
    per_prefill = _mean(r.spans_in_trace("prefill"), "experts_touched")
    if per_prefill is not None:
        touched += module_seconds(r.trace, PREFILL)[1] * per_prefill
    d = counts.dims(r.cfg)
    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = touched * d["moe_blocks"] * counts.expert_params(d) * width
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
