"""How close the paged decode kernel's latent variant comes to the HBM
bound: the latent rows the decode steps' rows NEEDED (each row's cached
tokens, ``batch`` x ``avg_ctx`` of the ``decode_window`` spans, a row of
``kv_lora_rank + qk_rope_head_dim`` = 576 values a token a layer, read
ONCE for both products: ``bytes_and_flops_mla.latent_bytes_per_token``)
over the peak bytes/s, as a share of the device time of the
``paged_decode_attention`` ops in the traced window. Steps are counted
as ``decode_step_device_ms`` counts them. A page's unused tail, the
pool's padded lanes (640 of 576) and a page fetched twice count nothing:
they show as lost share.

Where the configuration has no ``kv_lora_rank``, the trace has no
``paged_decode_attention`` op (``use_pallas`` off: the gathered XLA
form) or the spans carry no ``avg_ctx`` there is nothing to read."""

from .. import bytes_and_flops_mla as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "paged_decode_attention"


def read(r):
    if "kv_lora_rank" not in r.cfg or r.trace is None:
        return None
    seconds = sum(
        s for name, s in (r.trace.get("op_s") or {}).items() if OP in name
    )
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "avg_ctx" in s[3] and "batch" in s[3]]
    if seconds <= 0 or got is None or not spans:
        return None
    steps = [float(s[3].get("steps", 1)) for s in spans]
    tokens_a_step = sum(
        float(s[3]["batch"]) * float(s[3]["avg_ctx"]) * w
        for s, w in zip(spans, steps)
    ) / sum(steps)
    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = got[1] * tokens_a_step * counts.latent_bytes_per_token(r.cfg, width)
    least_s = total / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
