"""How close the delta-rule layers' commit of a window's accepted tokens
comes to the HBM bound: the bytes the traced window's commits had to
move (``kda_state_bytes`` of the ``decode_window`` spans, the rows'
matrices over every KDA layer, IN and OUT once a run of a decode
program, each of which commits once) over the peak bytes/s, as a share
of the device time of the ``kda_state_commit`` ops in the same window.
The tokens' ``(g, k, u)`` are left out, so the share is a lower bound.

Where the configuration has no ``linear_attn_config``, the trace has no
``kda_state_commit`` op (the gather, product and scatter ran) or the
spans carry no ``kda_state_bytes`` there is nothing to read."""

from .kda_state_read_hbm_roofline import op_seconds, per_step_state_bytes

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
OP = "kda_state_commit"
MODULES = r"decode"


def read(r):
    from ..trace_reduce import module_seconds

    if "linear_attn_config" not in r.cfg or r.trace is None:
        return None
    seconds = op_seconds(r, OP)
    _secs, runs = module_seconds(r.trace, MODULES)
    per_commit = per_step_state_bytes(r)
    if seconds <= 0 or runs <= 0 or per_commit is None:
        return None
    least_s = 2.0 * runs * per_commit / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
