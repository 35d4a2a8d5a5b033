"""Device time of the prefill programs (XLA modules whose name contains
``prefill``) in the traced window over the prompt tokens the flight
recorder's ``prefill`` spans say were prefilled in it."""

LAYER, UNIT, BETTER = "runner and model", "us/token", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
MODULES = r"prefill"


def read(r):
    from ..trace_reduce import module_seconds

    if r.trace is None:
        return None
    secs, _runs = module_seconds(r.trace, MODULES)
    tokens = sum(
        float(s[3].get("tokens", 0)) for s in r.spans_in_trace("prefill")
    )
    if secs <= 0 or tokens <= 0:
        return None
    return secs * 1e6 / tokens
