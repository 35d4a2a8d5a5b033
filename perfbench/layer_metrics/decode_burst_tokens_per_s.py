"""Output tokens a second while a job decodes: the tokens between the
first and the last progress update received inside the window over the
time between the two (``e2e_metrics/out_tokens_per_s_per_chip``'s
arithmetic), in a cell whose window holds one job's burst of updates
and long silences around it. There it is the pace of the decode
iterations alone (device step + the scheduler's work between steps),
and varies with the path the seed's weights take through the schema;
the job's set-up is not in it."""

from ..e2e_metrics import out_tokens_per_s_per_chip as rate

LAYER, UNIT, BETTER = "scheduler", "tokens/s", "higher"
SOURCE, MOVES = "host_clock", "job_turnaround_s"


def read(r):
    return rate.read(r)
