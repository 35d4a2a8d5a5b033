"""Slots of the mamba state pool that hold a live sequence, over the
slots there are (``sutro_state_slots{state="in_use"}`` over
``{state="total"}``), as the mean of the gauge at the window's start
and end and at every ``decode_window`` span in between (the span's
``state_rows`` are the slots its rows hold). Beside
``decode_batch_occupancy`` it says which of slots and pages bounds the
batch. A program without the gauge gives nothing to read."""

LAYER, UNIT, BETTER = "scheduler", "%", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
GAUGE = "sutro_state_slots"


def _gauge(reg, state):
    series = (reg.get(GAUGE) or {}).get("series", {})
    for key, value in series.items():
        if state in str(key):
            return float(value)
    return None


def read(r):
    total = _gauge(r.reg1, "total")
    if not total:
        return None
    held = [g for g in (_gauge(r.reg0, "in_use"), _gauge(r.reg1, "in_use"))
            if g is not None]
    held += [
        float(s[3]["state_rows"]) for s in r.window_spans
        if s[0] == "decode_window" and "state_rows" in s[3]
        and r.t0 <= s[1] <= r.t1
    ]
    if not held:
        return None
    return 100.0 * sum(held) / len(held) / total
