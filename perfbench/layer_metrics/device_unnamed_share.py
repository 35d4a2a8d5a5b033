"""Of the device's busy seconds in the traced window, every program, the
share in ops under NO part of the model (``trace_parts.py``): what a
``lax.scan`` does itself (slicing its xs, stacking its ys, counting),
what XLA hoisted out of a scope or gave no metadata, the step loops' own
token buffers. What ``sched_other_share`` is for the scheduler's thread.
Nothing to read when no op of the trace is under any part (a program
without the part scopes)."""

LAYER, UNIT, BETTER = "device", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    from ..trace_parts import seconds_by_part

    secs = seconds_by_part(r)
    total = sum(secs.values()) if secs else 0.0
    return 100.0 * secs.get(None, 0.0) / total if total > 0 else None
