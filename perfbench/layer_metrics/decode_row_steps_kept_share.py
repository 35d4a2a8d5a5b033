"""Of the row-steps the decode dispatches were made for, the share that
committed a token: ``sutro_sched_tokens_committed_total`` over
``sutro_sched_row_steps_total``, every path, the window's increments. A
row-step is one position of one live row in one dispatch (a fused
window's steps a row, the verify forward's width a row, one a row of a
single step); what is lost is counted by reason beside it
(``sutro_sched_row_steps_lost_total{path, reason}``): steps behind a
row's end (``finished``), a window in flight for a row that is gone
(``stale``), positions the FSM refused or a plan did not reach. A
program without the counters gives nothing to read."""

LAYER, UNIT, BETTER = "scheduler", "%", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
STEPS = "sutro_sched_row_steps_total"
COMMITTED = "sutro_sched_tokens_committed_total"
LOST = "sutro_sched_row_steps_lost_total"


def gained(r, name):
    """What the series of counter ``name`` gained over the window,
    summed over its paths."""
    keys = set()
    for reg in (r.reg0, r.reg1):
        keys |= set((reg.get(name) or {}).get("series", {}))
    return sum(r.counter_delta(name, key) for key in keys)


def read(r):
    if STEPS not in r.reg1 or COMMITTED not in r.reg1:
        return None
    steps = gained(r, STEPS)
    return 100.0 * gained(r, COMMITTED) / steps if steps > 0 else 0.0
