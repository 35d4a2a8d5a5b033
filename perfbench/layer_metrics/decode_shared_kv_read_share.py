"""Of the cached tokens the window's decode steps' attention layers
read, the share a layer read from ANOTHER layer's pool
(``sutro_kv_read_tokens_total{reader="shared"}`` over the counter's
whole increment, both pools, both readers): in a model whose cross
layers read one full layer's K/V again, 7 x full over 8 x full + 8 x
window. It says how much of a step the shared K/V is as the rows age (a
row under the window reads as much from each reader; past it the window
pool's reads stand still and the shared ones grow). A program without
the counter, or a model none of whose layers reads another's pool while
the counter stands at 0, gives nothing to read."""

LAYER, UNIT, BETTER = "runner and model", "%", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
COUNTER = "sutro_kv_read_tokens_total"


def read(r):
    if COUNTER not in r.reg1:
        return None
    series = (r.reg1.get(COUNTER) or {}).get("series", {})
    total = shared = 0.0
    for key in series:
        delta = r.counter_delta(COUNTER, key)
        total += delta
        if "shared" in str(key):
            shared += delta
    if total <= 0:
        return None
    return 100.0 * shared / total
