"""Bytes of delta-rule state the window's dispatches moved between HBM
and the chip over the bytes they had to move
(``sutro_kda_state_bytes_total``, both ``op`` series, over
``sutro_kda_state_bytes_needed_total``, the window's increments): a row's
slot read once a step and written once a commit is the need, 1.0 the
floor. A window of 8 steps under the two kernels reads 1.11 (the commit
streams the slot in as well as out); the XLA forms, which gather a
row's slot before they use it and scatter it back, read about 3.

Where the program has no such counters (no delta-rule layer ran) there
is nothing to read."""

LAYER, UNIT, BETTER = "runner and model", "ratio", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
MOVED = "sutro_kda_state_bytes_total"
NEEDED = "sutro_kda_state_bytes_needed_total"


def read(r):
    if not (r.reg1.get(NEEDED) or {}).get("series"):
        return None
    needed = r.counter_delta(NEEDED)
    if needed <= 0:
        return None
    moved = sum(r.counter_delta(MOVED, op) for op in ("read", "commit"))
    return moved / needed
