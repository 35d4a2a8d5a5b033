"""Backend compilations JAX reported inside the measured window
(``jax.monitoring``); should be 0."""

LAYER, UNIT, BETTER = "runner and model", "count", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"


def read(r):
    return float(sum(1 for t, _name, _s in r.compiles if r.t0 <= t <= r.t1))
