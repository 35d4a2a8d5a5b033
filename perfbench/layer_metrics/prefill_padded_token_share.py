"""The share of the prefill dispatches' token positions that held no
token: ``sutro_prefill_tokens_total{kind="padded"}`` over real + padded,
the window's increments. A batched prefill pads every row of a dispatch
to the bucket of its longest and the rows to a power of two; a row
alone pads to its own bucket. With short and long rows in one queue the
share says what the batching costs. A program without the counter gives
nothing to read."""

LAYER, UNIT, BETTER = "scheduler", "ratio", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
TOKENS = "sutro_prefill_tokens_total"


def read(r):
    if TOKENS not in r.reg1:
        return None
    padded = r.counter_delta(TOKENS, "padded")
    total = padded + r.counter_delta(TOKENS, "real")
    if total <= 0:
        return None
    return padded / total
