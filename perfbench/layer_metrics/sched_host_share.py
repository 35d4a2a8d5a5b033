"""Share of the window the scheduler thread spent in its own host work:
the seconds ``sutro_stage_seconds`` gained over the window in the
phases of HOST_PHASES (the phase cursor of engine/profiling.py; leaves
of one timeline, so the sum double counts nothing) over the window's
seconds. Device dispatch (``prefill``, ``decode_window``, ``admit``,
``kv_*``), the store's ``flush`` / ``finalize`` and ``sched_idle`` are
not in it. A program without the cursor observes none of these phases
and the metric is left out."""

LAYER, UNIT, BETTER = "scheduler", "%", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"

HOST_PHASES = (
    "sched_poll", "job_start", "admit_host", "constraint_compile",
    "fsm_mask", "fsm_plan", "batch_build", "accept", "emit",
    "sched_other",
)
# what only the cursor emits: their presence says the program has one
CURSOR_ONLY = tuple(
    p for p in HOST_PHASES if p not in ("constraint_compile", "accept")
)


def phase_seconds(r, phases):
    """(observations, seconds) the phases gained over the window."""
    n, secs = 0, 0.0
    for p in phases:
        dn, ds = r.hist_delta("sutro_stage_seconds", p)
        n, secs = n + dn, secs + ds
    return n, secs


def read(r):
    if phase_seconds(r, CURSOR_ONLY)[0] <= 0 or r.seconds <= 0:
        return None
    return 100.0 * phase_seconds(r, HOST_PHASES)[1] / r.seconds
