"""Output tokens committed inside the window, batch rows and chat
answers together, over the window's seconds and the chips the runner's
mesh spans. Counted from the client's side: the ``output_tokens`` totals
of every live job's progress stream plus chat token events, each stamped
at receipt. The totals arrive on the scheduler's 1 s ticks, so the rate
is taken between the first and the last update received inside the
window, not between its nominal edges."""

UNIT, BETTER, SOURCE = "tokens/s", "higher", "host_clock"


def read(r):
    pts = r.log.window_rate_points(r.t0, r.t1)
    if pts is None or pts[1] - pts[0] < 0.25:
        return None
    first, last, tokens = pts
    return tokens / (last - first) / r.n_chips
