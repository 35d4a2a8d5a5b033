"""How close a decode step of a model with Mamba-2 layers comes to the
HBM bound: the bytes one step must move
(``bytes_and_flops_ssm.decode_bytes_per_step``: the weights, K/V over
the attention layers, the state of the rows the step advances read
once) at the batch, mean context and ``state_rows`` the
``decode_window`` spans report, over the peak bytes/s, as a share of the
measured ``decode_step_device_ms``.

Where the configuration has no mamba layer or the spans carry no
``state_rows`` (a program without the state slots) there is nothing to
read."""

from .. import bytes_and_flops_ssm as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "mamba" not in (r.cfg.get("layer_types") or ()):
        return None
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "state_rows" in s[3]]
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    steps = [float(s[3].get("steps", 1)) for s in spans]

    def per_step_mean(key):
        return sum(
            float(s[3].get(key, 0)) * w for s, w in zip(spans, steps)
        ) / sum(steps)

    engine = r.cfg["engine"]
    width = {k: 2 if "16" in str(engine.get(k)) else 4
             for k in ("param_dtype", "activation_dtype")}
    total = counts.decode_bytes_per_step(
        r.cfg, batch=per_step_mean("batch"), mean_ctx=per_step_mean("avg_ctx"),
        state_rows=per_step_mean("state_rows"),
        weight_dtype_bytes=width["param_dtype"],
        kv_dtype_bytes=width["param_dtype"],
        state_dtype_bytes=width["activation_dtype"],
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
