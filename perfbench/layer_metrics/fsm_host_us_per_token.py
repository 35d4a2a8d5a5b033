"""Host microseconds of constrained decoding a committed token: the
seconds ``sutro_stage_seconds`` gained in ``fsm_mask`` + ``fsm_plan`` +
``constraint_compile`` + ``accept`` over the window's output tokens.
Left out where no mask was built and no jump planned (no constrained
row, or a program without the phase cursor)."""

from .sched_host_share import phase_seconds

LAYER, UNIT, BETTER = "scheduler", "us/token", "lower"
SOURCE, MOVES = "program_counter", "job_turnaround_s"

PHASES = ("fsm_mask", "fsm_plan", "constraint_compile", "accept")


def read(r):
    tokens = r.window_output_tokens()
    if not tokens or phase_seconds(r, ("fsm_mask", "fsm_plan"))[0] <= 0:
        return None
    return phase_seconds(r, PHASES)[1] * 1e6 / tokens
