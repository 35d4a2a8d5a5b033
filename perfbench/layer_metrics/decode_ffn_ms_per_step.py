"""Device ms a decode step spends in the blocks' feed-forward halves: the
self time of the decode programs' ops under the part ``ffn`` (the second
norm, the dense or routed MLP with its router, sort, grouped products,
shared expert and combine, the residual add, the routing counts;
``trace_parts.py``), over the decode steps. An addend of
``decode_step_device_ms`` (``decode_mixer_ms_per_step`` says which).
Nothing to read on a program without the part scopes."""

LAYER, UNIT, BETTER = "runner and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
PARTS = ("ffn",)


def read(r):
    from ..trace_parts import decode_part_ms_per_step

    return decode_part_ms_per_step(r, PARTS)
