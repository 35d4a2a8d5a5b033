"""Output tokens in the window over device dispatches: the change in the
observation COUNTS of ``sutro_stage_seconds{decode_window,admit}`` (a
count; the flight recorder's ring can wrap in a window). Fused windows
commit batch x steps tokens a dispatch; a constrained row that accepts
one token an iteration drags it down."""

LAYER, UNIT, BETTER = "scheduler", "tokens", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"


def read(r):
    tokens = r.window_output_tokens()
    n = sum(
        r.hist_delta("sutro_stage_seconds", st)[0]
        for st in ("decode_window", "admit")
    )
    if tokens is None or n <= 0:
        return None
    return tokens / n
