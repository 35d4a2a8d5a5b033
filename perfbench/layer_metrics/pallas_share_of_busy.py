"""Self time of the Mosaic custom calls (the Pallas kernels) over device
busy time, from the trace."""

LAYER, UNIT, BETTER = "kernels", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if r.trace is None or r.trace["busy_s"] <= 0:
        return None
    return 100.0 * r.trace["class_s"].get("pallas", 0.0) / r.trace["busy_s"]
