"""``sched_host_share`` (the scheduler thread's own host work as a share
of the window) for the cells whose end-to-end metric is the job's
turnaround: the same reader under the name of what it moves there."""

from . import sched_host_share

LAYER, UNIT, BETTER = "scheduler", "%", "lower"
SOURCE, MOVES = "program_counter", "job_turnaround_s"


def read(r):
    return sched_host_share.read(r)
