"""1 - (union of device-op intervals) / (traced window), averaged over
the chips used; the same two numbers are in the line's ``device``."""

LAYER, UNIT, BETTER = "device", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if r.trace is None or r.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
