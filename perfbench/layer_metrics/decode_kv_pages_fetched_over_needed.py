"""K/V pages the decode dispatches' attention fetched over the pages
their rows' visible tokens fill (``sutro_kv_pages_fetched_total`` over
``sutro_kv_pages_needed_total``, the window's increments): a row, a step
and an attention layer at a time, a window layer NEEDING its window's
pages and no more. About 1.1 when a window layer fetches from the page of
its oldest visible position on (whole pages for a window that lies
across them), about 1.4 at contexts of 1-3k when it fetches the whole
context and masks it. Only a model that keeps K/V a pool a kind (the
``sutro_kv_pages`` gauge) gives something to read: elsewhere the ratio is
the paged kernel's page rounding, which other metrics hold."""

LAYER, UNIT, BETTER = "kernels", "ratio", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
FETCHED = "sutro_kv_pages_fetched_total"
NEEDED = "sutro_kv_pages_needed_total"
GAUGE = "sutro_kv_pages"


def read(r):
    if not (r.reg1.get(GAUGE) or {}).get("series"):
        return None
    needed = r.counter_delta(NEEDED)
    if needed <= 0:
        return None
    return r.counter_delta(FETCHED) / needed
