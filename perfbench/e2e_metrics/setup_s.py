"""Process start to the window's start: imports, engine, weights, pool,
cache loads or compilation, the warm set, the reference check and the
lead-in."""

UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(r):
    return r.startup_seconds
