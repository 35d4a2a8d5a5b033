"""Device time of the hyper-connections over device busy time: the self
time, in the traced window, of every device op that holds an
instruction under a scope that starts with ``hc_`` (``hc_coeff``: the
norm statistic, the projection and the sigmoids; ``hc_sinkhorn``: the
twenty passes; ``hc_read``: the sublayer's input from the lanes;
``hc_write``: the new lanes), over ``reduce_trace``'s ``busy_s``. What
the changed residual path costs of the whole, whatever implements it;
the paper's own figure for four lanes is 6.7 % of a training step.

An op counts WHOLE or not at all: its time is what the trace measured,
never a share reckoned from its instructions. The program keeps the
stream's passes device programs of their own (``hc_sublayer`` puts the
sublayer's input and output behind an ``optimization_barrier``, and
``tests/perfbench/test_aot_xing_v5e.py`` finds no fusion of the compiled
programs that holds an ``hc_`` instruction beside another scope's), so
the seconds are the stream's and nothing else's. Should a compiler fuse
a pass with a neighbour all the same, the neighbour's time comes with
it: this share is then an UPPER bound and ``mhc_stream_hbm_roofline`` a
lower one, and ``seconds_by_kind`` says how much of the time lies in
such ops. Ops and their self time are ``trace_parts.op_rows``'.

Where the trace holds no such op (a program without the scopes, a model
of one lane) or does not describe its modules there is nothing to
read."""

import functools
import time

LAYER, UNIT, BETTER = "runner and model", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"
SCOPE = "hc_"


def under_hc(op_name: str) -> bool:
    from ..trace_parts import scopes_of

    return any(s.startswith(SCOPE) for s in scopes_of(op_name).split("/"))


def hc_ops(protos):
    """``{module: {instruction: "own" | "shared"}}`` of the instructions
    that are under an ``hc_`` scope or fuse one that is: ``own`` where
    every instruction that carries a part of the model is, ``shared``
    where another scope's rides along."""
    from ..trace_parts import instructions, part_of

    out = {}
    for module, proto in protos.items():
        table = {}
        for name, (op_name, fused, _operands) in instructions(proto).items():
            names = [n for n in (fused or [op_name]) if part_of(n)]
            hc = sum(1 for n in names if under_hc(n))
            if hc:
                table[name] = "own" if hc == len(names) else "shared"
        out[module] = table
    return out


@functools.lru_cache(maxsize=1)
def seconds_by_kind(path: str, window_ns):
    """``{"own": s, "shared": s}``: the window's device seconds in ops
    wholly under an ``hc_`` scope and in ops that hold one beside
    another scope's instructions."""
    from .. import trace_parts

    trace, _names = trace_parts.parsed(path)
    # ``op_rows`` hands back what it is given for an op's name: its kind
    rows = trace_parts.op_rows(
        trace, hc_ops(trace_parts.hlo_protos(path)), window_ns
    )
    out = {"own": 0.0, "shared": 0.0}
    for _key, _name, kind, secs in rows:
        if kind:
            out[kind] += secs
    return out


def hc_seconds(r):
    """Device seconds of the ops that hold an ``hc_`` instruction in the
    traced window; None without a trace, 0.0 where no op does."""
    from ..trace_parts import newest_trace

    if r.trace is None or "window_ns" not in r.trace:
        return None
    # the trace was written after the window began, on the wall clock
    path = newest_trace(time.time() - (time.monotonic() - r.t0))
    if path is None:
        return None
    return sum(seconds_by_kind(path, tuple(r.trace["window_ns"])).values())


def read(r):
    if r.trace is None or r.trace.get("busy_s", 0) <= 0:
        return None
    secs = hc_seconds(r)
    if not secs:
        return None
    return 100.0 * secs / r.trace["busy_s"]
