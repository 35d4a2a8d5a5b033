"""How close the prefill programs of a model whose residual stream is
several lanes round latent-attention layers come to the MXU bound: the
operations the prefilled rows need
(``bytes_and_flops_mhc.prefill_flops_per_row`` at each row's OWN length,
from the ``tokens`` of the flight recorder's ``prefill`` spans in the
traced window: the expanded form, a head's K 192 and V 128 wide, the
causal half of the square, ``num_experts_per_tok`` experts a token, the
hyper-connections' projection, read and mix; nothing padded) over the
peak bf16 operations/s, as a share of the device time of the prefill
programs (XLA modules whose name contains ``prefill``) in the same
window. A row padded to its bucket, a head padded for a kernel's tiles,
the upper half of the square computed and masked, and the stream's
passes, which are bytes and no operations to speak of, all show as lost
share.

Where the configuration has no ``hc_mult``, or the window has no prefill
span with ``tokens`` or no prefill program, there is nothing to read."""

from .. import bytes_and_flops_mhc as counts

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if "hc_mult" not in r.cfg or r.trace is None:
        return None
    secs, _runs = module_seconds(r.trace, MODULES)
    rows = [float(s[3]["tokens"]) for s in r.spans_in_trace("prefill")
            if float(s[3].get("tokens", 0)) > 0]
    if secs <= 0 or not rows:
        return None
    flops = sum(counts.prefill_flops_per_row(r.cfg, n) for n in rows)
    least_s = flops / r.n_chips / r.peaks()["bf16_flops_per_s"]
    return 100.0 * least_s / secs
