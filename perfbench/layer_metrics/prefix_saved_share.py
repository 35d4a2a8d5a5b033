"""Prefill tokens the prefix store saved
(``sutro_prefix_store_prefill_tokens_saved_total``) over the prompt
tokens submitted in the window: the ``input_tokens`` the jobs' progress
streams reported plus the prompt tokens of the chats that ended in it."""

LAYER, UNIT, BETTER = "scheduler", "%", "higher"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"


def read(r):
    series = [
        (t, n) for t, n in r.log.cumulative_tokens(which=3)
        if r.t0 <= t <= r.t1
    ]
    if len(series) < 2:
        return None
    prompt = series[-1][1] - series[0][1] + sum(
        int(c["prompt_tokens"] or 0) for c in r.log.chats
        if not c["warm"] and c["done"] is not None and r.t0 <= c["done"] <= r.t1
    )
    if prompt <= 0:
        return None
    saved = r.counter_delta("sutro_prefix_store_prefill_tokens_saved_total")
    return 100.0 * saved / prompt
