#!/usr/bin/env python3
"""Where the scheduler thread's time went in one traced window of a cell,
by phase: what PERF.md's section 5 tables are copied from.

    python3 perfbench/tools/phase_table.py --workload <cell> --seed <n>

Runs the cell exactly as ``perfbench/run.py --trace 1`` does (same
warm-up, window and tracer) and prints, over the whole window: wall
seconds, observations and summed ``cpu_s`` of every stage of
``sutro_stage_seconds`` (registry deltas; ``cpu_s`` from the
flight-recorder spans still in the ring), scheduler iterations by path,
rows a dispatch; and over the traced part: the device's idle seconds
split by overlap across the recorder spans under them, and how far each
phase's ``sutro/<stage>`` annotation on the profiler's own clock lies
from the recorder span it mirrors (the check of the harness's sync
offset). The JSON goes to ``chiprun_out/perfbench/<cell>.phases.json``.

Needs the chip, like run.py; ``--cpu-rehearsal`` runs the tiny cells and
prints no device number.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import run, trace_reduce  # noqa: E402
from perfbench.layer_metrics import idle_unattributed_share  # noqa: E402

PREFIX = "sutro/"


def annotations(xplane: str):
    """{stage: [(start_ns, end_ns)]} of the ``sutro/<stage>`` events on
    the host planes, per thread line."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.setdefault((line.name, ev.name[len(PREFIX):]), []).append(
                        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                    )
    return out


def disagreement(reading, raw):
    """Per stage, the largest distance between a recorder span's END and
    the nearest end of an annotation of the same stage (ends: a span's
    start may hold a folded sliver of up to 50 us, its end never)."""
    offset_ns = trace_reduce.mono_offset_ns(raw)
    if offset_ns is None:
        return None
    by_stage, lines = {}, set()
    for (line, stage), evs in annotations(raw["xplane"]).items():
        lines.add(line)
        by_stage.setdefault(stage, []).extend(
            (e + offset_ns) * 1e-9 for _s, e in evs
        )
    lo, hi = reading.trace_span
    worst = {}
    for name, _a, b, attrs in reading.spans:
        ends = by_stage.get(name)
        # only what the cursor emitted (its spans carry cpu_s): a
        # flush or finalize span is the jobstore's own, on any thread
        if not ends or "cpu_s" not in attrs or not (lo + 0.01 <= b <= hi - 0.01):
            continue
        ends.sort()
        i = bisect.bisect_left(ends, b)
        near = min(abs(ends[j] - b) for j in (i - 1, i) if 0 <= j < len(ends))
        w = worst.setdefault(name, [0, 0.0, []])
        w[0] += 1
        w[1] = max(w[1], near)
        w[2].append(near)
    return {
        "thread_lines": sorted(lines),
        "annotated_stages": sorted(by_stage),
        "by_stage": {
            k: {"spans": n, "max_us": mx * 1e6,
                "median_us": sorted(ds)[len(ds) // 2] * 1e6}
            for k, (n, mx, ds) in worst.items()
        },
        "max_us": max((w[1] * 1e6 for w in worst.values()), default=None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    traffic_dir = HERE / "traffic"
    if args.cpu_rehearsal:
        cells = json.loads((HERE / "rehearsal" / "cells.json").read_text())
        bench = dict(bench, configs=cells["configs"], workloads=cells["workloads"])
        traffic_dir = HERE / "rehearsal" / "traffic"
    cell, cfg = run.load_cell(bench, args.workload)
    seconds = float(args.seconds or bench["run_seconds"])

    from perfbench import sut as sut_mod

    try:
        sut = sut_mod.System(cfg, args.seed, args.cpu_rehearsal)
    except sut_mod.NoDevice as e:
        print(str(e), file=sys.stderr)
        return 3
    try:
        r, _env, _problems, _facts, raw = run.measure(
            sut, cfg, cell["traffic"], traffic_dir, args.seed, seconds, True,
        )
    finally:
        sut.close()

    stages = sorted(
        set((r.reg1.get("sutro_stage_seconds") or {}).get("series", {}))
    )
    cpu, in_ring = {}, {}
    for name, a, _b, attrs in r.spans:
        if r.t0 <= a < r.t1:
            cpu[name] = cpu.get(name, 0.0) + float(attrs.get("cpu_s", 0.0))
            in_ring[name] = in_ring.get(name, 0) + 1
    phases = {}
    for st in stages:
        n, secs = r.hist_delta("sutro_stage_seconds", st)
        if n:
            phases[st] = {"n": n, "wall_s": secs, "spans_in_ring": in_ring.get(st, 0),
                          "cpu_s_in_ring": cpu.get(st)}
    paths = {
        k: r.counter_delta("sutro_sched_iterations_total", k)
        for k in (r.reg1.get("sutro_sched_iterations_total") or {}).get("series", {})
    }
    doc = {
        "workload": cell["name"], "seed": args.seed, "window_s": r.seconds,
        "output_tokens": r.window_output_tokens(), "phases": phases,
        "iterations": paths,
        "dispatch_rows": r.counter_delta("sutro_sched_dispatch_rows_total"),
        "spans_in_ring": len(r.spans),
    }
    # host clocks against each other: not a device number
    doc["annotation_vs_recorder"] = disagreement(r, raw)
    # the ring as the trace's end saw it, seconds from the window's start
    doc["trace_span"] = [r.trace_span[0] - r.t0, r.trace_span[1] - r.t0]
    doc["timeline"] = [
        [name, round(a - r.t0, 6), round(b - a, 6), attrs.get("cpu_s"),
         {k: v for k, v in attrs.items() if k not in ("jobs", "cpu_s")}]
        for name, a, b, attrs in r.spans
    ]
    if not args.cpu_rehearsal:
        split = idle_unattributed_share.split_by_phase(r)
        doc["trace"] = {
            "window_s": r.trace["window_s"], "busy_s": r.trace["busy_s"],
            "idle_by_phase_s": dict(sorted(split.items(), key=lambda kv: -kv[1])),
            "modules": r.trace["module_s"],
        }
    tag = run.REHEARSAL_TAG if args.cpu_rehearsal else ""
    print(tag + f"{cell['name']} seed {args.seed}: window {r.seconds:.1f} s, "
          f"{doc['output_tokens']} output tokens")
    print(tag + f"{'stage':22s}{'n':>8s}{'wall_s':>10s}{'cpu_s(ring)':>13s}")
    for st, p in sorted(phases.items(), key=lambda kv: -kv[1]["wall_s"]):
        c = "" if p["cpu_s_in_ring"] is None else f"{p['cpu_s_in_ring']:.3f}"
        print(tag + f"{st:22s}{p['n']:8d}{p['wall_s']:10.3f}{c:>13s}")
    print(tag + "iterations " + json.dumps(paths)
          + f" rows {doc['dispatch_rows']:.0f}")
    print(tag + "annotation_vs_recorder "
          + json.dumps(doc["annotation_vs_recorder"]))
    if "trace" in doc:
        print(json.dumps(doc["trace"], default=str))
    out = REPO / "chiprun_out" / "perfbench"
    try:
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{cell['name']}.phases.json").write_text(
            json.dumps(doc, indent=1, default=str)
        )
    except OSError as e:
        print(f"phase_table: could not write: {e}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_code)
