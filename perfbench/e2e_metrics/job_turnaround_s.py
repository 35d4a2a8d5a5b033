"""Seconds from a batch client's submit to the end of its job, as the
caller of ``so.infer`` / ``so.classify`` waits for its DataFrame: the
median over the jobs that ended SUCCEEDED inside the window (submitted
in the lead-in or in the window; warm jobs are not in it). Everything a
job pays is in it: the submit's feasibility probe, the session's
set-up, admission, decode, the flush. Left out where no job ended in
the window."""

import statistics

UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(r):
    took = [
        j["ended"] - j["submitted"] for j in r.log.jobs
        if not j["warm"] and j["status"] == "SUCCEEDED"
        and j["ended"] is not None and r.t0 <= j["ended"] <= r.t1
    ]
    return statistics.median(took) if took else None
