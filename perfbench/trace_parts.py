"""Which PART of the model issued each device op of a profiler trace.

The program opens one ``jax.named_scope`` of a fixed vocabulary round
everything a step does (``sutro_tpu/ops/lowering.py`` ``PARTS``; the
copy below is held equal by a test). A scope ends up in the ``op_name``
metadata of the optimized HLO, and a profiler trace carries that HLO
itself: its plane ``/host:metadata`` holds one event metadata a module
that ran (``jit__decode_multi_jit(1234)``, the name of its ``XLA
Modules`` events), each with a stat ``Hlo Proto``, the serialized
``HloProto`` of the OPTIMIZED module. A v5e's ``XLA Ops`` events name an
op by its HLO line without the metadata and carry no ``op_name`` stat
(looked at by hand, PERF.md section 6 PR 52), so the metadata plane is
the one source. ``jax.profiler.ProfileData`` does not expose event
metadata, but the protobuf wire format is a page of code: nothing here
needs more than JAX, no dump directory, no flag set before JAX loads,
nothing asked of the program but the names. A trace of a program
without the scopes reads by the same code and gives None.

How an op gets its part: ``part_of`` of the ``op_name`` of the
instruction that has the op's name in the module that covers the op's
start. A FUSION goes to the part of the fusion instruction's own
``op_name``; where that holds no part (the compiler gave the fusion no
metadata, or that of a root the scan wrote), to the part most of its
fused instructions carry. An instruction a compiler pass made and named
itself (``ragged-dot-none``: the expansion of a ``ragged_dot``) or left
without a name goes to the part most of its OPERANDS carry. What XLA
fuses across two parts goes to one of them whole; what it hoists out of
a scope, or what a ``lax.scan`` does itself (slicing its xs, stacking
its ys, counting: a JAX path with no scope in it), has no part.
"""

from __future__ import annotations

import bisect
import functools
import re
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import trace_reduce

#: the program's ``sutro_tpu.ops.lowering.PARTS``
PARTS = ("embed", "mixer", "ffn", "cache", "head", "sample")

METADATA_PLANE = "/host:metadata"

#: path components of an ``op_name`` that are JAX's own, not a scope the
#: program opened (``scopes_of``)
_STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "pjit", "shard_map",
    "checkpoint", "remat", "custom_jvp_call", "custom_vjp_call", "core_call",
))
_BARE = re.compile(r"^[a-z_][a-z0-9_]*$")
_BRANCH = re.compile(r"^branch_\d+_fun$")


# -- the protobuf wire format ----------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one serialized message: an int for a
    varint, a memoryview for a length-delimited or fixed-width value
    (nested messages are walked by calling this again on the view)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield num, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _first(buf, number: int):
    return next((v for k, v in fields(buf) if k == number), None)


def hlo_protos(xplane_path: str) -> Dict[str, bytes]:
    """``{module name: serialized HloProto}`` from the trace's
    ``/host:metadata`` plane. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 (a map: value = 2); XEventMetadata.name = 2,
    .stats = 5; XStat.bytes_value = 6 (str_value = 5)."""
    out: Dict[str, bytes] = {}
    space = Path(xplane_path).read_bytes()
    for num, plane in fields(space):
        if num != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name).decode() != METADATA_PLANE:
            continue
        for k, entry in fields(plane):
            if k != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            module = proto = None
            for kk, v in fields(meta):
                if kk == 2:
                    module = bytes(v).decode()
                elif kk == 5:
                    for k3, v3 in fields(v):
                        if k3 in (5, 6) and len(v3) > 64:
                            proto = bytes(v3)
            if module and proto:
                out[module] = proto
    return out


def _ints(v) -> List[int]:
    """A repeated int64 field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        c, i = _varint(v, i)
        out.append(c)
    return out


def instructions(hlo_proto: bytes) -> Dict[str, Tuple[str, List[str], List[str]]]:
    """``{instruction name: (op_name, the op_names of what it fuses, the
    names of its operands)}`` over every computation of an
    ``HloProto``. HloProto.hlo_module = 1; HloModuleProto.computations =
    3; HloComputationProto.instructions = 2, .id = 5;
    HloInstructionProto.name = 1, .opcode = 2, .metadata = 7
    (OpMetadata.op_name = 2), .id = 35, .operand_ids = 36,
    .called_computation_ids = 38."""
    module = _first(hlo_proto, 1)
    if module is None:
        return {}
    comps: Dict[int, List[Tuple[str, str, str, List[int], List[int]]]] = {}
    by_id: Dict[int, str] = {}
    for k, comp in fields(module):
        if k != 3:
            continue
        cid, rows = None, []
        for kk, v in fields(comp):
            if kk == 5:
                cid = v
            elif kk == 2:
                name = opcode = op_name = ""
                called: List[int] = []
                operands: List[int] = []
                for k3, v3 in fields(v):
                    if k3 == 1:
                        name = bytes(v3).decode()
                    elif k3 == 2:
                        opcode = bytes(v3).decode()
                    elif k3 == 7:
                        got = _first(v3, 2)
                        op_name = "" if got is None else bytes(got).decode()
                    elif k3 == 35:
                        by_id[v3] = name
                    elif k3 == 36:
                        operands += _ints(v3)
                    elif k3 == 38:
                        called += _ints(v3)
                rows.append((name, opcode, op_name, called, operands))
        comps[cid] = rows

    def fused(called: List[int], depth: int = 0) -> List[str]:
        out: List[str] = []
        for c in called:
            for _n, opcode, op_name, inner, _o in comps.get(c, ()):
                out.append(op_name)
                if opcode == "fusion" and depth < 4:
                    out += fused(inner, depth + 1)
        return out

    return {
        name: (op_name, fused(called) if opcode == "fusion" else [],
               [by_id.get(i, "") for i in operands])
        for rows in comps.values()
        for name, opcode, op_name, called, operands in rows
    }


def part_of(op_name: str) -> Optional[str]:
    """The first bare path component that is one of ``PARTS``:
    ``jit(f)/while/body/mixer/attn_mixer/dot_general`` -> ``mixer``
    (the outermost scope is the part; ``jit(sample)`` is not bare)."""
    for piece in (op_name or "").split("/"):
        if piece in PARTS:
            return piece
    return None


def scopes_of(op_name: str) -> str:
    """The named scopes INSIDE the part, ``/``-joined
    (``mla_mixer/mla_absorb/dsa_attend``; "" directly under the part):
    the bare components between the part and the primitive that are not
    JAX's own (``while``, ``body``, ``closed_call``, ``jit(...)``, ...)
    and not a part again (``cache`` inside ``cache``: a write that calls
    another); a scope repeated in a row (a scan inside it restates its
    name stack) counts once."""
    pieces = (op_name or "").split("/")
    at = next((i for i, p in enumerate(pieces) if p in PARTS), None)
    if at is None:
        return ""
    scopes: List[str] = []
    for p in pieces[at + 1:-1]:
        if (_BARE.match(p) and p not in _STRUCTURAL and p not in PARTS
                and not _BRANCH.match(p) and scopes[-1:] != [p]):
            scopes.append(p)
    return "/".join(scopes)


def _most(names: List[str]) -> Optional[str]:
    """The first of ``names`` that carries the part most of them carry
    (a tie goes to the part named first in ``PARTS``)."""
    count: Dict[str, int] = {}
    for name in names:
        p = part_of(name)
        if p:
            count[p] = count.get(p, 0) + 1
    if not count:
        return None
    best = max(count, key=lambda p: (count[p], -PARTS.index(p)))
    return next(n for n in names if part_of(n) == best)


def read_as(op_name: str, fused: List[str], operands: List[str] = ()) -> str:
    """The ``op_name`` an instruction is read under: its own where that
    holds a part; else (a fusion) that of what it fuses, by majority;
    else, where its own is no JAX path at all (a compiler pass made the
    instruction and named it itself, ``ragged-dot-none``, or gave it
    none), that of its operands, by majority; else its own."""
    if part_of(op_name):
        return op_name
    inner = _most(fused)
    if inner is None and "/" not in op_name:
        inner = _most(list(operands))
    return inner or op_name


def hlo_op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name it is read under}}`` of
    every module the trace describes (``read_as``); {} for a trace
    without a metadata plane."""
    out: Dict[str, Dict[str, str]] = {}
    for module, proto in hlo_protos(xplane_path).items():
        found = instructions(proto)
        # an operand is read as its own name or what it fuses says: one hop
        own = {n: read_as(op, fused) for n, (op, fused, _o) in found.items()}
        out[module] = {
            name: read_as(op_name, fused, [own.get(o, "") for o in operands])
            for name, (op_name, fused, operands) in found.items()
        }
    return out


# -- from the trace's events to seconds a part -------------------------------

def op_rows(
    trace: Dict[str, Any], names: Dict[str, Dict[str, str]],
    window_ns: Tuple[float, float],
) -> List[Tuple[str, str, str, float]]:
    """``(module key, op's own name, op_name, self seconds)`` of every op
    of a neutral-form trace (``trace_reduce.load_xplane``) inside
    ``window_ns``, the seconds averaged over the device planes as
    ``reduce_trace`` does. SELF time (``trace_reduce.self_times``: a
    ``while`` does not count its body twice), cut to the window in
    proportion where an op straddles its edge. An op's module is the
    ``XLA Modules`` event that covers its start ("" outside any)."""
    lo, hi = window_ns
    n_dev = max(len(trace["devices"]), 1)
    by_key: Dict[str, Dict[str, str]] = {}
    for module, ops in names.items():
        by_key.setdefault(trace_reduce.module_key(module), {}).update(ops)
    out = []
    for _plane, dev in sorted(trace["devices"].items()):
        runs = sorted((m[1], m[1] + m[2], m[0]) for m in dev["modules"])
        starts = [r[0] for r in runs]
        ops = [ev for ev in dev["ops"] if ev[1] + ev[2] > lo and ev[1] < hi]
        for ev, self_ns in zip(ops, trace_reduce.self_times(ops)):
            i = bisect.bisect_right(starts, ev[1]) - 1
            module = runs[i][2] if i >= 0 and ev[1] < runs[i][1] else ""
            key = trace_reduce.module_key(module)
            inside = min(ev[1] + ev[2], hi) - max(ev[1], lo)
            if ev[2] > 0:
                self_ns *= inside / ev[2]
            known = names.get(module) or by_key.get(key) or {}
            out.append(
                (key, ev[0], known.get(ev[0], ""), self_ns * 1e-9 / n_dev)
            )
    return out


def of_modules(rows, pattern: Optional[str]):
    """The rows of the programs whose key matches ``pattern`` (all: None)."""
    if not pattern:
        return rows
    rx = re.compile(pattern)
    return [x for x in rows if rx.search(x[0])]


def by_part(rows) -> Dict[Optional[str], float]:
    out: Dict[Optional[str], float] = {}
    for _key, _name, op_name, secs in rows:
        p = part_of(op_name)
        out[p] = out.get(p, 0.0) + secs
    return out


def newest_trace(since: float = 0.0) -> Optional[str]:
    """The newest ``.xplane.pb`` written at or after ``since``
    (``time.time()``) where ``run.Tracer`` puts it (README.md: profiler
    traces go under ``TMPDIR``). ``Reading`` does not carry the path
    (PERF.md section 7 asks the next benchmark PR to put it there)."""
    found = [
        p for p in Path(tempfile.gettempdir()).glob(
            "perfbench-trace-*/plugins/profile/*/*.xplane.pb"
        ) if p.stat().st_mtime >= since
    ]
    return str(max(found, key=lambda p: p.stat().st_mtime)) if found else None


@functools.lru_cache(maxsize=1)
def parsed(path: str):
    """``(neutral trace, hlo_op_names)`` of a trace file, parsed once a
    process: the readers and the tool share it."""
    return trace_reduce.load_xplane(path), hlo_op_names(path)


@functools.lru_cache(maxsize=1)
def _rows(path: str, window_ns: Tuple[float, float]):
    trace, names = parsed(path)
    return op_rows(trace, names, window_ns)


def rows_of(r, modules: Optional[str] = None):
    """``op_rows`` of a traced ``Reading``'s own trace file (made once a
    process; ``modules`` keeps the programs whose key matches); None in
    an untraced run or when the file is not found."""
    if r.trace is None:
        return None
    # the trace was written after the window began, on the wall clock
    path = newest_trace(time.time() - (time.monotonic() - r.t0))
    if path is None:
        return None
    return of_modules(_rows(path, tuple(r.trace["window_ns"])), modules)


def seconds_by_part(
    r, modules: Optional[str] = None
) -> Optional[Dict[Optional[str], float]]:
    """``{part | None: device seconds}`` in the traced window of a
    ``Reading`` (None: under no part), optionally of the programs whose
    key matches ``modules`` alone. None when no op of them is under
    ``mixer``, which every step program of a tree with the parts has: a
    program without the scopes (the parent of PR 52; JAX itself names a
    few of its ops ``.../sample/reduce``, so "no part at all" would not
    say it), or a trace that does not describe its modules."""
    rows = rows_of(r, modules)
    if rows is None:
        return None
    got = by_part(rows)
    return got if got.get("mixer") else None


def decode_part_ms_per_step(r, parts: Tuple[str, ...]) -> Optional[float]:
    """Device ms a decode step under ``parts``: their seconds in the
    decode programs over the decode steps, both as
    ``decode_step_device_ms`` takes them."""
    from .layer_metrics import decode_step_device_ms as whole

    got = whole.steps_and_seconds(r)
    secs = seconds_by_part(r, whole.MODULES)
    if got is None or secs is None:
        return None
    return sum(secs.get(p, 0.0) for p in parts) * 1e3 / got[1]
