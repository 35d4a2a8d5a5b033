"""Rows a decode dispatch carried over the rows it could have:
``sutro_sched_dispatch_rows_total`` over (the iterations of
``sutro_sched_iterations_total`` whose path is not ``idle`` x the
configuration's ``decode_batch_size``), over the window."""

LAYER, UNIT, BETTER = "scheduler", "%", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"

PATHS = ("pipelined", "window", "fastforward", "spec", "multi", "single")


def read(r):
    iterations = sum(
        r.counter_delta("sutro_sched_iterations_total", p) for p in PATHS
    )
    batch = float((r.cfg.get("engine") or {}).get("decode_batch_size") or 0)
    if iterations <= 0 or batch <= 0:
        return None
    rows = r.counter_delta("sutro_sched_dispatch_rows_total")
    return 100.0 * rows / (iterations * batch)
