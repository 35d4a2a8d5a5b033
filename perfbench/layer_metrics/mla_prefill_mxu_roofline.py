"""How close the prefill programs of a model of latent-attention layers
come to the MXU bound: the operations the prefilled rows need
(``bytes_and_flops_mla.prefill_flops_per_row`` at each row's OWN length,
from the ``tokens`` of the flight recorder's ``prefill`` spans in the
traced window: the expanded form, a head's K 192 and V 128 wide, the
causal half of the square, nothing padded) over the peak bf16
operations/s, as a share of the device time of the prefill programs
(XLA modules whose name contains ``prefill``) in the same window. A row
padded to its bucket, a head padded for a kernel's tiles or the upper
half of the square computed and masked all show as lost share.

Where the configuration has no ``kv_lora_rank``, or the window has no
prefill span with ``tokens`` or no prefill program, there is nothing to
read."""

from .. import bytes_and_flops_mla as counts

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if "kv_lora_rank" not in r.cfg or r.trace is None:
        return None
    secs, _runs = module_seconds(r.trace, MODULES)
    rows = [float(s[3]["tokens"]) for s in r.spans_in_trace("prefill")
            if float(s[3].get("tokens", 0)) > 0]
    if secs <= 0 or not rows:
        return None
    flops = sum(counts.prefill_flops_per_row(r.cfg, n) for n in rows)
    least_s = flops / r.n_chips / r.peaks()["bf16_flops_per_s"]
    return 100.0 * least_s / secs
