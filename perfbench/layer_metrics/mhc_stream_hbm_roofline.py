"""How close the hyper-connections' passes over the residual stream come
to the HBM bound: the bytes the stream of the traced window's tokens
MUST move (the ``hc_stream_bytes`` of the flight recorder's ``prefill``
and ``decode_window`` spans in the window: every sublayer reads the
lanes once and writes them once, ``2 n C`` elements a token; a tile of
tokens stays on the chip between the coefficients, the read and the mix,
and the sublayer's input and output are the sublayer's own traffic) over
the peak bytes/s, as a share of the device time of the ops that hold an
instruction under an ``hc_`` scope in the same window
(``mhc_share_of_busy.hc_seconds``: whole ops, the time the trace
measured). The new device work's own roofline share, whatever implements
it: the coefficients' small programs (the projection, forty Sinkhorn
fusions a sublayer), a lane read a second and a third time, the
sublayer's input written and its output read again and a decode batch's
padding rows all show as lost share; a kernel that reads the lanes once
and writes them once reads 100 %.

Where the configuration has no ``hc_mult``, the trace holds no op under
such a scope or the window no span with the bytes there is nothing to
read."""

from .mhc_share_of_busy import hc_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def bytes_in_trace(r) -> float:
    return sum(
        float(s[3].get("hc_stream_bytes", 0))
        for name in ("prefill", "decode_window")
        for s in r.spans_in_trace(name)
    )


def read(r):
    if "hc_mult" not in r.cfg or r.trace is None:
        return None
    secs, needed = hc_seconds(r), bytes_in_trace(r)
    if not secs or needed <= 0:
        return None
    least_s = needed / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
