"""Share of the window in which a job-scope constraint build was
running: the flight recorder's ``constraint_prep`` and
``constraint_compile`` spans whose ``scope`` is ``job`` (the submit's
feasibility probe, the session's schema index, a build on the attach
thread), each clipped to the window, summed, over the window's seconds.
Builds on different threads at once count twice, so it can pass 100.
Read from the recorder as it stands at the window's end, a span still
running counted up to there. Left out where the window holds no such
span."""

LAYER, UNIT, BETTER = "engine", "%", "lower"
SOURCE, MOVES = "program_span", "job_turnaround_s"

NAMES = ("constraint_prep", "constraint_compile")


def read(r):
    secs, seen = 0.0, False
    for name, start, end, attrs in r.window_spans or r.spans:
        if name in NAMES and attrs.get("scope") == "job":
            overlap = min(end, r.t1) - max(start, r.t0)
            if overlap > 0:
                secs, seen = secs + overlap, True
    if not seen or r.seconds <= 0:
        return None
    return 100.0 * secs / r.seconds
