"""Device ms a decode step spends in the sequence mixers: the self time of
the decode programs' ops under the part ``mixer`` (a block's input norm,
its attention or state mixer of whatever kind, the output projection,
the residual add; ``trace_parts.py``), over the decode steps. One of
the addends of ``decode_step_device_ms`` (same programs, same steps):
with ``decode_ffn_ms_per_step``, ``decode_head_ms_per_step``, the parts
``cache`` and ``embed`` and the unnamed rest it sums to it. Nothing to
read on a program without the part scopes."""

LAYER, UNIT, BETTER = "runner and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
PARTS = ("mixer",)


def read(r):
    from ..trace_parts import decode_part_ms_per_step

    return decode_part_ms_per_step(r, PARTS)
