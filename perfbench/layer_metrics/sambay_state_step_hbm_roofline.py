"""How close the Mamba-1 layers' state step comes to the HBM bound: the
bytes the traced window's decode steps had to move for the state
(``bytes_and_flops_sambay.state_bytes_per_step``: ``state_layers``
layers' slots, the matrix ``[16, 5120]`` and the conv columns, for
``batch`` rows, read AND written a step, in the dtype the slot keeps)
over the peak bytes/s, as a share of the self time of the device ops
under the ``mamba1_state_step`` scope of the decode programs in the
same window (``trace_parts.op_rows``; an op counts whole where it holds
an instruction of the scope). The program carries a window's state in
float32 (twice the slot's bytes) and may fuse the step with a
neighbour: both show as lost share, so the share is a lower bound.

Where the configuration is no ``phi4flash``, the trace describes no op
under the scope or the spans carry no ``state_layers`` there is nothing
to read."""

from .. import bytes_and_flops_sambay as counts
from .decode_step_device_ms import MODULES, steps_and_seconds
from .sambay_decode_hbm_roofline import (
    is_family, per_step_mean, width, window_spans,
)

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
SCOPE = "mamba1_state_step"


def scope_seconds(r):
    from ..trace_parts import rows_of

    rows = rows_of(r, MODULES)
    if rows is None:
        return 0.0
    return sum(
        secs for _key, _name, op_name, secs in rows
        if SCOPE in (op_name or "").split("/")
    )


def read(r):
    if not is_family(r) or r.trace is None:
        return None
    seconds = scope_seconds(r)
    got = steps_and_seconds(r)
    spans, steps = window_spans(r)
    if seconds <= 0 or got is None or not spans:
        return None
    a_step = counts.state_bytes_per_step(
        r.cfg, state_dtype_bytes=width(r),
        **{k: per_step_mean(spans, steps, k) for k in ("batch", "state_layers")},
    )
    least_s = got[1] * a_step / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
