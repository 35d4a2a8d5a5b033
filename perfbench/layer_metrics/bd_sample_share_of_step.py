"""The share of the decode programs' device time that a model that
generates by blocks spends SAMPLING: the self time of the ops under the
part ``sample`` (the draw over ``[rows x block, V]``, the confidences,
``bd_confidence``, and the transfer, ``bd_transfer``; ``trace_parts.py``)
over that of every op of the decode programs, in the traced window. A
commit forward samples nothing, so at ``S`` denoising forwards a block
the share is diluted by ``S / (S + 1)``.

Where the configuration has no ``block_length`` or the program has no
part scopes there is nothing to read."""

LAYER, UNIT, BETTER = "runner and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    from ..trace_parts import seconds_by_part
    from .decode_step_device_ms import MODULES

    if "block_length" not in r.cfg:
        return None
    secs = seconds_by_part(r, MODULES)
    if not secs:
        return None
    whole = sum(secs.values())
    return None if whole <= 0 else 100.0 * secs.get("sample", 0.0) / whole
