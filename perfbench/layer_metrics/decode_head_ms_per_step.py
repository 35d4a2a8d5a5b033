"""Device ms a decode step spends behind the last block: the self time
of the decode programs' ops under the parts ``head`` (the final norm and
the vocabulary-wide product) and ``sample`` (penalties, masks, the
top-k head, the draw, the log-probability; ``trace_parts.py``), over
the decode steps. An addend of ``decode_step_device_ms``
(``decode_mixer_ms_per_step`` says which). Nothing to read on a program
without the part scopes."""

LAYER, UNIT, BETTER = "runner and model", "ms", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
PARTS = ("head", "sample")


def read(r):
    from ..trace_parts import decode_part_ms_per_step

    return decode_part_ms_per_step(r, PARTS)
