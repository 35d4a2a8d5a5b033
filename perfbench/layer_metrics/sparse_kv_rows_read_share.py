"""The share of the cached rows that the decode dispatches' attention
read: ``sutro_sparse_attention_rows_total{kind="selected"}`` over
``{kind="context"}``, the window's increments (a row-step at a time,
host arithmetic from the rows' lengths: the rows a query's context holds
against ``min(context, index_topk)``). 0.28 at a mean context of 7,400
and an ``index_topk`` of 2,048; 1.0 would mean every row was at or under
``index_topk`` and the dense path ran. Only a program that counts such
rows (a model whose latent layers have an indexer) gives something to
read."""

LAYER, UNIT, BETTER = "runner and model", "ratio", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"
ROWS = "sutro_sparse_attention_rows_total"


def read(r):
    context = r.counter_delta(ROWS, "context")
    if context <= 0:
        return None
    return r.counter_delta(ROWS, "selected") / context
