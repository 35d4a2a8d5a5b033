"""Prompt tokens the program prefilled AGAIN because a path could not
restore a sequence's conv state at the position it resumed from
(``sutro_state_fallback_prefill_tokens_total``, every reason), over the
prompt tokens submitted in the window (the ``input_tokens`` the jobs'
progress streams reported). 0 in a traffic that preempts nothing and
demotes nothing: the metric guards the path. A program without the
counter gives nothing to read."""

LAYER, UNIT, BETTER = "scheduler", "%", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
COUNTER = "sutro_state_fallback_prefill_tokens_total"


def read(r):
    if COUNTER not in r.reg1:
        return None
    keys = set((r.reg0.get(COUNTER) or {}).get("series", {})) | set(
        r.reg1[COUNTER].get("series", {})
    )
    again = sum(r.counter_delta(COUNTER, k) for k in keys)
    series = [
        (t, n) for t, n in r.log.cumulative_tokens(which=3)
        if r.t0 <= t <= r.t1
    ]
    prompt = series[-1][1] - series[0][1] if len(series) >= 2 else 0
    if prompt <= 0:
        # nothing submitted in the window: none prefilled again is 0 %
        return 0.0 if again <= 0 else None
    return 100.0 * again / prompt
