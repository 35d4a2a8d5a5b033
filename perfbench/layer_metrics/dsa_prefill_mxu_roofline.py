"""How close the prefill programs of a model of latent-attention layers
under an indexer's selection come to the MXU bound: the operations the
prefilled rows NEED (``bytes_and_flops_dsa.prefill_flops_per_row`` at
each row's OWN length, from the ``tokens`` of the flight recorder's
``prefill`` spans in the traced window: projections, experts and head,
the indexer's scores over the causal half of the square, the attention's
two products over ``min(t + 1, index_topk)`` keys a query, nothing
padded) over the peak bf16 operations/s, as a share of the device time
of the prefill programs (XLA modules whose name contains ``prefill``) in
the same window. A row padded to its bucket and, above all, the dense
masked products over every causal pair where ``index_topk`` keys a query
are needed show as lost share: the headroom of a gathered sparse prefill.

Where the configuration has no ``index_topk``, or the window has no
prefill span with ``tokens`` or no prefill program, there is nothing to
read."""

from .. import bytes_and_flops_dsa as counts

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if "index_topk" not in r.cfg or r.trace is None:
        return None
    secs, _runs = module_seconds(r.trace, MODULES)
    rows = [float(s[3]["tokens"]) for s in r.spans_in_trace("prefill")
            if float(s[3].get("tokens", 0)) > 0]
    if secs <= 0 or not rows:
        return None
    flops = sum(counts.prefill_flops_per_row(r.cfg, n) for n in rows)
    least_s = flops / r.n_chips / r.peaks()["bf16_flops_per_s"]
    return 100.0 * least_s / secs
