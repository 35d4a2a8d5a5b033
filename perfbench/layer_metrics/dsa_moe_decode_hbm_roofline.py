"""How close a decode step of a model of latent-attention layers under
an indexer's selection, with a held share of routed experts, comes to
the HBM bound: the bytes one step must move
(``bytes_and_flops_dsa.decode_bytes_per_step``: every layer's attention
and indexer projections, dense FFN, router, shared expert and norms and
the head, of the held experts those the step's rows chose, each row's
index keys, 128 wide, over its whole context and its
``min(context, index_topk)`` selected latent rows, 576 wide, read once)
at the batch, mean context and ``experts_touched`` the ``decode_window``
spans report, over the peak bytes/s, as a share of the measured
``decode_step_device_ms``. The scores, the top-k, a gathered copy of the
index keys or of the selected rows count nothing: they show as lost
share.

Where the configuration has no ``index_topk`` or the spans carry no
``experts_touched`` (a program without the routing counts) there is
nothing to read."""

from .. import bytes_and_flops_dsa as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "index_topk" not in r.cfg:
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

    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = counts.decode_bytes_per_step(
        r.cfg, batch=per_step_mean("batch"), mean_ctx=per_step_mean("avg_ctx"),
        experts_touched=per_step_mean("experts_touched"),
        weight_dtype_bytes=width, kv_dtype_bytes=width,
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
