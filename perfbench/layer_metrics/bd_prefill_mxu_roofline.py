"""How close the prefill programs of a model that generates by blocks
come to the MXU bound: the operations the prefilled rows need
(``bytes_and_flops_bd.prefill_flops_per_row`` at each row's OWN length,
from the ``tokens`` and ``batch`` of the flight recorder's ``prefill``
spans in the traced window: every position's products with ``top_k``
experts, the head on one position, QK^T and PV over the keys the block
mask shows, nothing padded) over the peak bf16 operations/s, as a share
of the device time of the prefill programs (XLA modules whose name
contains ``prefill``) in the same window. A row padded to its bucket
and the masked half of the square show as lost share.

Where the configuration has no ``block_length``, or the window has no
prefill span with ``tokens`` or no prefill program, there is nothing to
read."""

from .. import bytes_and_flops_bd as counts

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if "block_length" not in r.cfg or r.trace is None:
        return None
    secs, _runs = module_seconds(r.trace, MODULES)
    flops = 0.0
    for s in r.spans_in_trace("prefill"):
        tokens = float(s[3].get("tokens", 0))
        rows = max(float(s[3].get("batch", 1)), 1.0)
        if tokens > 0:
            flops += rows * counts.prefill_flops_per_row(r.cfg, tokens / rows)
    if secs <= 0 or flops <= 0:
        return None
    least_s = flops / r.n_chips / r.peaks()["bf16_flops_per_s"]
    return 100.0 * least_s / secs
