"""Self time of all-reduce / all-gather / reduce-scatter ops over device
busy time, from the trace; only a mesh has any."""

LAYER, UNIT, BETTER = "collectives", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if r.trace is None or r.trace["busy_s"] <= 0 or r.n_chips < 2:
        return None
    return 100.0 * r.trace["class_s"].get("collective", 0.0) / r.trace["busy_s"]
