"""How close a decode step of a model of delta-rule (KDA) and gated GQA
layers with a held share of routed experts comes to the HBM bound: the
bytes one step must move
(``bytes_and_flops_kda.decode_bytes_per_step``: the mixers', routers',
shared experts' and norms' weights and the head, of the held experts
those the step's rows chose, K/V over the GQA layers, the delta-rule
state of the rows the step advances READ once a step and WRITTEN once a
window of the spans' ``steps``) at the batch, mean context,
``state_rows`` and ``experts_touched`` the ``decode_window`` spans
report, over the peak bytes/s, as a share of the measured
``decode_step_device_ms``.

Where the configuration has no ``linear_attn_config`` or the spans carry
no ``kda_state_bytes`` or no ``experts_touched`` (a program without the
delta-rule slots or the routing counts) there is nothing to read."""

from .. import bytes_and_flops_kda as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "linear_attn_config" not in r.cfg:
        return None
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "kda_state_bytes" in s[3] and "experts_touched" in s[3]]
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    steps = [float(s[3].get("steps", 1)) for s in spans]

    def per_step_mean(key):
        return sum(
            float(s[3].get(key, 0)) * w for s, w in zip(spans, steps)
        ) / sum(steps)

    engine = r.cfg["engine"]
    width = {k: 2 if "16" in str(engine.get(k, engine.get("param_dtype"))) else 4
             for k in ("param_dtype", "activation_dtype")}
    total = counts.decode_bytes_per_step(
        r.cfg, batch=per_step_mean("batch"), mean_ctx=per_step_mean("avg_ctx"),
        state_rows=per_step_mean("state_rows"),
        experts_touched=per_step_mean("experts_touched"),
        # a window commits once: its steps share one write
        steps_per_commit=sum(steps) / len(steps),
        weight_dtype_bytes=width["param_dtype"],
        kv_dtype_bytes=width["param_dtype"],
        state_dtype_bytes=width["activation_dtype"],
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
