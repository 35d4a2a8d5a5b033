"""How close the prefill programs of a model whose layer kinds differ in
their query heads come to the MXU bound: the operations the prefilled
rows need (``bytes_and_flops_laguna.prefill_flops`` at each row's OWN
length, the ``row_tokens`` of the flight recorder's ``prefill`` spans in
the traced window: REAL tokens, nothing padded) over the peak bf16
operations/s, as a share of the device time of the prefill programs
(XLA modules whose name contains ``prefill``) in the same window. A row
padded to the bucket of its dispatch's longest, the rows padded to a
power of two and the masked half of the square show as lost share.

Where the configuration has no ``num_attention_heads_per_layer``, or the
window has no prefill span with ``row_tokens`` (a program that does not
say them) or no prefill program, there is nothing to read."""

from .. import bytes_and_flops_laguna as counts

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if "num_attention_heads_per_layer" not in r.cfg or r.trace is None:
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
