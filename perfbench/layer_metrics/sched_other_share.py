"""What the scheduler's instrumentation itself fails to name: seconds
of ``sched_other`` over the seconds of every phase of the scheduler
thread's timeline in the window, device-dispatch stages and
``sched_idle`` included."""

from .sched_host_share import CURSOR_ONLY, HOST_PHASES, phase_seconds

LAYER, UNIT, BETTER = "scheduler", "%", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"

TIMELINE = HOST_PHASES + (
    "prefill", "decode_window", "admit", "kv_demote", "kv_promote",
    "sched_idle",
)


def read(r):
    if phase_seconds(r, CURSOR_ONLY)[0] <= 0:
        return None
    total = phase_seconds(r, TIMELINE)[1]
    if total <= 0:
        return None
    return 100.0 * phase_seconds(r, ("sched_other",))[1] / total
