"""From a profiler trace to numbers: busy/idle union, per-module and
per-op sums, Pallas and collective shares, idle gaps by what the host
was doing. Pure functions over a small neutral form, so the same code
reduces a live ``.xplane.pb`` (through ``load_xplane``) and the small
recorded trace the tests keep (``data/recorded_trace.json``).

Neutral form::

    {"devices": {"<plane name>": {"ops": [[name, start_ns, dur_ns, text], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "sync": [[trace_ns, mono_ns], ...]}

``text`` is whatever else the trace says about the op (category, source
op name), lower-cased, used only to classify it. ``sync`` pairs a time
on the trace's clock with ``time.monotonic_ns()`` of the same instant
(a ``TraceAnnotation`` the harness writes), so host-clock spans can be
laid over device gaps.

On a TPU the "XLA Ops" line nests: a ``while`` (the layer scan, the
step scan) is one event that covers the events of its body. Sums of
durations would count the body twice, so every per-op number here is
SELF time: an event's duration minus the events nested directly in it.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

_CLASSES_FILE = Path(__file__).resolve().parent / "trace_classes.json"
SYNC_NAME = "perfbench_sync"

Interval = Tuple[float, float]


# -- loading ---------------------------------------------------------------

def load_xplane(path: str) -> Dict[str, Any]:
    """Read a ``.xplane.pb`` with nothing but JAX into the neutral form.
    Device planes are those named ``/device:...`` (a TPU's are
    ``/device:TPU:<n>``); their op and module lines are matched by name.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "sync": []}
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                lname = line.name.lower()
                if lname in ("xla ops", "ops"):
                    for ev in line.events:
                        short, text = split_hlo(ev.name)
                        dev["ops"].append(
                            [short, float(ev.start_ns),
                             float(ev.duration_ns), text]
                        )
                elif lname in ("xla modules", "modules"):
                    for ev in line.events:
                        dev["modules"].append(
                            [ev.name, float(ev.start_ns),
                             float(ev.duration_ns)]
                        )
            if dev["ops"] or dev["modules"]:
                out["devices"][name] = dev
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC_NAME:
                        mono = dict(ev.stats).get("mono_ns")
                        if mono is not None:
                            out["sync"].append(
                                [float(ev.start_ns), float(mono)]
                            )
    return out


_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def split_hlo(event_name: str) -> Tuple[str, str]:
    """A TPU trace names an op by its whole HLO line
    (``%fusion.7 = bf16[..] fusion(bf16[..] %all-reduce.3, ...), ...``).
    Returns the op's own name (``fusion.7``) and what classifies it: its
    opcode and, for a custom call, its target. Operands are left out, so
    an op that merely READS a collective's result is not one."""
    head, sep, rest = event_name.partition(" = ")
    short = head.strip().lstrip("%")
    if not sep:
        return short, ""
    op = _OPCODE.search(rest)
    target = _TARGET.search(rest)
    text = " ".join(
        x for x in (op.group(1) if op else "",
                    target.group(1) if target else "") if x
    )
    return short, text.lower()


def describe_xplane(path: str, per_line: int = 12) -> Dict[str, Any]:
    """Plane, line and a few event names with their stats: what to read
    by hand before trusting a reduction of a new kind of trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    doc = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = []
            n = 0
            for ev in line.events:
                n += 1
                if len(evs) < per_line:
                    evs.append(
                        {"name": ev.name, "start_ns": ev.start_ns,
                         "dur_ns": ev.duration_ns,
                         "stats": {k: str(v)[:120] for k, v in ev.stats}}
                    )
            lines[line.name] = {"events": n, "first": evs}
        doc[plane.name] = lines
    return doc


# -- interval arithmetic ---------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals (overlapping or touching ones join)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] left by ``merged`` (already a
    union, clipped to the window)."""
    out = []
    at = lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Sequence[Any]]) -> List[float]:
    """Self time of each event of ONE line, in the order given: its
    duration minus the durations of the events nested directly inside
    it. Events are ``[name, start, dur, ...]``; an event nests in
    another when it starts inside it and ends no later."""
    order = sorted(
        range(len(events)), key=lambda i: (events[i][1], -events[i][2])
    )
    selfs = [float(ev[2]) for ev in events]
    stack: List[int] = []
    for i in order:
        s, d = float(events[i][1]), float(events[i][2])
        while stack:
            p = stack[-1]
            if s >= events[p][1] + events[p][2]:
                stack.pop()
            else:
                break
        if stack and s + d <= events[stack[-1]][1] + events[stack[-1]][2]:
            selfs[stack[-1]] -= d
        stack.append(i)
    return [max(x, 0.0) for x in selfs]


# -- classification --------------------------------------------------------

def load_classes() -> Dict[str, List[str]]:
    return json.loads(_CLASSES_FILE.read_text())["classes"]


def classify(name: str, text: str, classes: Dict[str, List[str]]) -> Optional[str]:
    hay = f"{name} {text}".lower()
    for cls, needles in classes.items():
        if any(n in hay for n in needles):
            return cls
    return None


_ID_SUFFIX = re.compile(r"\(\d+\)$")


def module_key(name: str) -> str:
    """``jit__decode_multi_jit(123456)`` -> ``jit__decode_multi_jit``."""
    return _ID_SUFFIX.sub("", name).strip()


def op_key(name: str) -> str:
    """``fusion.123`` -> ``fusion``: instances of one kind add up."""
    return re.sub(r"[.\d]+$", "", name) or name


# -- the reduction ---------------------------------------------------------

def window_of(trace: Dict[str, Any]) -> Tuple[float, float]:
    """The traced window on the trace's clock: between the first and
    the last sync mark when the harness wrote two, else the span of the
    device events."""
    marks = sorted(t for t, _m in trace.get("sync", []))
    if len(marks) >= 2:
        return marks[0], marks[-1]
    starts, ends = [], []
    for dev in trace["devices"].values():
        for ev in dev["ops"] or dev["modules"]:
            starts.append(ev[1])
            ends.append(ev[1] + ev[2])
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def reduce_trace(trace: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the per-layer readers and ``breakdown`` need. Times
    are seconds, averaged over the device planes present (one a chip).
    """
    classes = load_classes()
    lo, hi = window_of(trace)
    n_dev = max(len(trace["devices"]), 1)
    busy = 0.0
    by_class: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    by_module: Dict[str, Dict[str, float]] = {}
    first_gaps: List[Interval] = []
    for idx, (_plane, dev) in enumerate(sorted(trace["devices"].items())):
        ops = [ev for ev in dev["ops"] if ev[1] + ev[2] > lo and ev[1] < hi]
        merged = union(clip(((ev[1], ev[1] + ev[2]) for ev in ops), lo, hi))
        busy += total(merged)
        if idx == 0:
            first_gaps = gaps(merged, lo, hi)
        for ev, self_ns in zip(ops, self_times(ops)):
            by_op[op_key(ev[0])] = by_op.get(op_key(ev[0]), 0.0) + self_ns
            cls = classify(ev[0], ev[3] if len(ev) > 3 else "", classes)
            if cls:
                by_class[cls] = by_class.get(cls, 0.0) + self_ns
        for name, s, d in dev["modules"]:
            if s + d <= lo or s >= hi:
                continue
            m = by_module.setdefault(module_key(name), {"s": 0.0, "n": 0.0})
            m["s"] += min(s + d, hi) - max(s, lo)
            # a run counts where it STARTS in the window
            m["n"] += 1.0 if lo <= s < hi else 0.0
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n_dev,
        "n_devices": len(trace["devices"]),
        "class_s": {k: v * ns / n_dev for k, v in by_class.items()},
        "op_s": {k: v * ns / n_dev for k, v in by_op.items()},
        "module_s": {
            k: {"s": v["s"] * ns / n_dev, "runs": v["n"] / n_dev}
            for k, v in by_module.items()
        },
        "gaps_ns": first_gaps,
        "window_ns": (lo, hi),
    }


def mono_offset_ns(trace: Dict[str, Any]) -> Optional[float]:
    """``mono_ns - trace_ns``: add to a time on the trace's clock to get
    ``time.monotonic_ns()``. Median over the sync marks."""
    ds = sorted(m - t for t, m in trace.get("sync", []))
    return ds[len(ds) // 2] if ds else None


def attribute_gaps(
    gaps_ns: Sequence[Interval], offset_ns: Optional[float],
    spans: Sequence[Tuple[str, float, float]], top: int = 10,
) -> List[List[Any]]:
    """Idle seconds by what the host was doing. ``spans`` are
    ``(name, start_mono_s, end_mono_s)`` on the monotonic clock; a gap
    goes to the span that covers its midpoint (the shortest such span,
    so a nested span wins), else to ``unattributed``."""
    agg: Dict[str, float] = {}
    for s, e in gaps_ns:
        name = "unattributed"
        if offset_ns is not None:
            mid = ((s + e) / 2.0 + offset_ns) * 1e-9
            best = None
            for sp_name, a, b in spans:
                if a <= mid <= b and (best is None or b - a < best[1]):
                    best = (sp_name, b - a)
            if best:
                name = best[0]
        agg[name] = agg.get(name, 0.0) + (e - s) * 1e-9
    ranked = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]


def top_ops(reduced: Dict[str, Any], top: int = 10) -> List[List[Any]]:
    ranked = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]


def module_seconds(reduced: Dict[str, Any], pattern: str) -> Tuple[float, float]:
    """(device seconds, runs) of the modules whose name matches the
    regular expression ``pattern``."""
    rx = re.compile(pattern)
    s = n = 0.0
    for name, m in reduced["module_s"].items():
        if rx.search(name):
            s += m["s"]
            n += m["runs"]
    return s, n
