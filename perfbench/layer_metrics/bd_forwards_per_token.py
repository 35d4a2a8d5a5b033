"""Forwards a row that a model that generates by blocks pays for a token
of output: ``sutro_block_row_forwards_total`` (both kinds: a denoising
forward fills positions, a commit forward keeps the block's K/V; forwards
x the window's live rows) over ``sutro_block_tokens_total{accepted}``,
the window's increments. With ``S`` denoising forwards a block of ``Bk``
and nothing lost it is ``(S + 1) / Bk``: 0.75 at 2 of 4. What a row
loses behind its stop token or its cap, the prompt's leftover tokens at
the head of a first block and windows in flight for a row that is gone
raise it: what an acceptance rate is to a model that drafts. A program
without the counters gives nothing to read."""

LAYER, UNIT, BETTER = "scheduler", "forwards/token", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
FORWARDS = "sutro_block_row_forwards_total"
TOKENS = "sutro_block_tokens_total"


def read(r):
    if FORWARDS not in r.reg1 or TOKENS not in r.reg1:
        return None
    tokens = r.counter_delta(TOKENS, "accepted")
    if tokens <= 0:
        return None
    return sum(
        r.counter_delta(FORWARDS, kind) for kind in ("denoise", "commit")
    ) / tokens
