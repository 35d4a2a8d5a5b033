"""Device time of the decode programs (XLA modules whose name contains
``decode``) in the traced window, over the decode steps they ran: runs
counted in the trace, times the mean ``steps`` of the flight recorder's
``decode_window`` spans in the same window (8 on the fused path, 1 when
a constrained row forces single steps)."""

LAYER, UNIT, BETTER = "runner and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"decode"


def steps_and_seconds(r):
    from ..trace_reduce import module_seconds

    if r.trace is None:
        return None
    secs, runs = module_seconds(r.trace, MODULES)
    spans = r.spans_in_trace("decode_window")
    steps = [float(s[3].get("steps", 1)) for s in spans]
    if runs <= 0 or secs <= 0 or not steps:
        return None
    return secs, runs * sum(steps) / len(steps)


def read(r):
    got = steps_and_seconds(r)
    return None if got is None else got[0] * 1e3 / got[1]
