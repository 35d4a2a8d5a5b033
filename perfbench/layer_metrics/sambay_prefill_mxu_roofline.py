"""How close the prefill programs of a decoder-hybrid-decoder come to
the MXU bound: the operations the prefilled rows need
(``bytes_and_flops_sambay.prefill_flops`` at each row's OWN length, the
``row_tokens`` of the flight recorder's ``prefill`` spans in the traced
window: REAL tokens, nothing padded, the differential attention's
products as the model needs them and not as the pair form pads them)
over the peak bf16 operations/s, as a share of the device time of the
prefill programs (XLA modules whose name contains ``prefill``) in the
same window. A row padded to its bucket, the masked half of the square,
the pair form's zero halves and the Mamba-1 scan (elementwise: no MXU
work to speak of) show as lost share.

Where the configuration is no ``phi4flash``, or the window has no
prefill span with ``row_tokens`` or no prefill program, there is nothing
to read."""

from .. import bytes_and_flops_sambay as counts
from .sambay_decode_hbm_roofline import is_family

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if not is_family(r) or r.trace is None:
        return None
    secs, _runs = module_seconds(r.trace, MODULES)
    flops = sum(
        counts.prefill_flops(r.cfg, s[3]["row_tokens"])
        for s in r.spans_in_trace("prefill") if "row_tokens" in s[3]
    )
    if secs <= 0 or flops <= 0:
        return None
    least_s = flops / r.n_chips / r.peaks()["bf16_flops_per_s"]
    return 100.0 * least_s / secs
