"""Host seconds the engine spent tokenizing, flushing and finalizing
(``sutro_stage_seconds{tokenize,flush,finalize}``) over the rows that
finished in the window (``sutro_rows_total{ok}``)."""

LAYER, UNIT, BETTER = "engine", "us/row", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"


def read(r):
    rows = r.counter_delta("sutro_rows_total", "ok")
    if rows <= 0:
        return None
    secs = sum(
        r.hist_delta("sutro_stage_seconds", st)[1]
        for st in ("tokenize", "flush", "finalize")
    )
    return secs * 1e6 / rows
