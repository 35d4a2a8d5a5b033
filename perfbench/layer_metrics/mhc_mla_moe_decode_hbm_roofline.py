"""How close a decode step of a model whose residual stream is several
lanes round latent-attention layers and routed experts, every one held,
comes to the HBM bound: the bytes one step must move
(``bytes_and_flops_mhc.decode_bytes_per_step``: every layer's attention
projections, dense FFN, router, shared expert, norms and
hyper-connections and the head, of the experts those the step's rows
chose, each row's cached latent rows, 576 wide, read once, and the rows'
stream, every lane read once and written once a sublayer: the spans'
``hc_stream_bytes``) at the batch, mean context and ``experts_touched``
the ``decode_window`` spans report, over the peak bytes/s, as a share of
the measured ``decode_step_device_ms``. A gathered copy of the pages, a
page padded for a kernel's tiles or a lane read a second time counts
nothing: it shows as lost share.

Where the configuration has no ``hc_mult`` or the spans carry no
``experts_touched`` or no ``hc_stream_bytes`` (a program without the
counts) there is nothing to read."""

from .. import bytes_and_flops_mhc as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "hc_mult" not in r.cfg:
        return None
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "experts_touched" in s[3] and "hc_stream_bytes" in s[3]]
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
        # a span's bytes are its whole window's: ``steps`` steps
        stream_bytes=sum(float(s[3]["hc_stream_bytes"]) for s in spans)
        / sum(steps),
        weight_dtype_bytes=width, kv_dtype_bytes=width,
    )
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
