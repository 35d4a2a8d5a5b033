"""The straggler a routed layer waits for: the rows the busiest expert
got over the rows an expert got on average, both as the program counted
them on the device inside each decode step (the ``decode_window`` spans'
``expert_rows_max`` and ``expert_rows_mean``: means over routed layers
and the window's steps), over the spans of the traced window weighted by
their steps. 1.0 is a perfectly even routing. A program whose spans carry
no such attrs gives nothing to read."""

LAYER, UNIT, BETTER = "runner and model", "ratio", "lower"
SOURCE, MOVES = "program_span", "out_tokens_per_s_per_chip"


def read(r):
    spans = [
        s for s in r.spans_in_trace("decode_window")
        if "expert_rows_max" in s[3] and "expert_rows_mean" in s[3]
    ]
    if not spans:
        return None
    steps = [float(s[3].get("steps", 1)) for s in spans]
    top = sum(float(s[3]["expert_rows_max"]) * w for s, w in zip(spans, steps))
    mean = sum(float(s[3]["expert_rows_mean"]) * w for s, w in zip(spans, steps))
    return None if mean <= 0 else top / mean
