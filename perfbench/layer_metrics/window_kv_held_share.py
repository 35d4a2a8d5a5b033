"""The share of its K/V a window layer keeps: pages of the window
layers' pool the decode batch's rows hold, over the pages the same rows'
whole contexts fill (``sutro_kv_window_pages_held_total`` over
``sutro_kv_window_pages_whole_total``, both summed a scheduler iteration,
the window's increments). Under 1 where pages go back as they slide out
of the window (about window / context, plus a page of misalignment and
one of tokens in flight); 1 would be one pool for both kinds. A program
without the counters gives nothing to read."""

LAYER, UNIT, BETTER = "scheduler", "ratio", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
HELD = "sutro_kv_window_pages_held_total"
WHOLE = "sutro_kv_window_pages_whole_total"


def read(r):
    if HELD not in r.reg1 or WHOLE not in r.reg1:
        return None
    whole = r.counter_delta(WHOLE)
    if whole <= 0:
        return None
    return r.counter_delta(HELD) / whole
