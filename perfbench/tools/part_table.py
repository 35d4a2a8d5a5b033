#!/usr/bin/env python3
"""Where the device's time went in one traced window of a cell, a
program, BY PART of the model: what PERF.md's section 5 by-part lines
are copied from.

    python3 perfbench/tools/part_table.py --workload <cell> --seed <n>
    python3 perfbench/tools/part_table.py --xplane <file.xplane.pb>

Runs the cell exactly as ``perfbench/run.py --trace 1`` does (same
warm-up, window and tracer) and prints, a program of the trace
(``jit__decode_multi_jit``, ``jit__decode_jit``, ``jit__verify_cand_jit``,
``jit__prefill_jit``, ...): its runs in the traced window, device ms a
run (a STEP for the fused windows: a run over the largest ``steps`` of
the window's ``decode_window`` spans), the ms by part
(``perfbench/trace_parts.py``: the program's ``PARTS``, self time), under
each part its inner scopes (``attn_mixer``, ``moe_ffn/shared_expert``,
...), the largest kinds of op of each part, and the largest ops of the
unnamed rest under their own HLO names with the ``op_name`` that says why
they have no part. Then the four
per-layer metrics this table stands behind as their readers give them
(``decode_mixer_ms_per_step``, ``decode_ffn_ms_per_step``,
``decode_head_ms_per_step``, ``device_unnamed_share``) beside
``decode_step_device_ms``. The JSON goes to
``chiprun_out/perfbench/<cell>.parts.json``.

It WARNS when a decode program of the trace holds none of the parts: a
scope is debug info, which the persistent compile cache's key leaves
out, so a checkout whose ``.xla_cache/`` (or ``JAX_COMPILATION_CACHE_DIR``)
was warmed before the scopes were added runs the old names until the
cache is cleared (OBSERVABILITY.md "Parts of a step").

``--xplane`` reduces a trace that is already there (an
``EngineConfig.profile_dir`` job trace):
every program over the whole trace (between the harness's marks where it
wrote them), ms a run, or a step of ``--steps`` a fused run. Needs the chip to run a cell, like run.py;
``--cpu-rehearsal`` runs the tiny cells to debug the flow (a CPU trace
has no device plane: the table is empty).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import run, trace_parts, trace_reduce  # noqa: E402

FUSED = r"decode_(multi|window)"
UNNAMED = "(no part)"


def table(rows, runs, steps=1, top=8):
    """``{program: {runs, unit, ms, by_part_ms, by_scope_ms,
    unnamed_ops_ms}}`` from ``trace_parts.op_rows`` and the runs of each
    program (``reduce_trace``'s ``module_s``)."""
    per_program = {}
    for key, name, op_name, secs in rows:
        per_program.setdefault(key, []).append((name, op_name, secs))
    doc = {}
    for key, ops in sorted(per_program.items()):
        n = (runs.get(key) or {}).get("runs", 0.0)
        fused = bool(re.search(FUSED, key))
        per = max(n, 1.0) * (steps if fused else 1)
        by_part, by_scope, unnamed, kinds = {}, {}, {}, {}
        for name, op_name, secs in ops:
            part = trace_parts.part_of(op_name) or UNNAMED
            by_part[part] = by_part.get(part, 0.0) + secs
            kind = kinds.setdefault(part, {})
            k = trace_reduce.op_key(name)
            kind[k] = kind.get(k, 0.0) + secs
            if part == UNNAMED:
                u = unnamed.setdefault(name, [0.0, op_name])
                u[0] += secs
            else:
                scope = part + "/" + trace_parts.scopes_of(op_name)
                by_scope[scope] = by_scope.get(scope, 0.0) + secs

        def ms(d):
            return {
                k: round(v * 1e3 / per, 4)
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            }

        doc[key] = {
            "runs": n, "unit": "step" if fused else "run",
            "ms": round(sum(by_part.values()) * 1e3 / per, 4),
            "by_part_ms": ms(by_part),
            "by_scope_ms": ms(by_scope),
            # the largest kinds of op of each part (``fusion``, a kernel)
            "top_ops_ms": {
                p: dict(list(ms(k).items())[:top]) for p, k in kinds.items()
            },
            "unnamed_ops_ms": [
                [name, round(s * 1e3 / per, 4), op_name]
                for name, (s, op_name) in sorted(
                    unnamed.items(), key=lambda kv: -kv[1][0]
                )[:top]
            ],
        }
    return doc


def stale(doc):
    """The decode programs of a table without a ``mixer``, which every
    step program of a tree with the parts has."""
    return [
        key for key, p in doc.items()
        if "decode" in key and "mixer" not in p["by_part_ms"]
    ]


def show(doc, say=print):
    for key, p in sorted(doc.items(), key=lambda kv: -kv[1]["ms"] * kv[1]["runs"]):
        say(f"{key}: {p['runs']:.0f} runs, {p['ms']:.3f} ms a {p['unit']}")
        say("  " + "  ".join(f"{k} {v:.3f}" for k, v in p["by_part_ms"].items()))
        for scope, v in p["by_scope_ms"].items():
            say(f"    {scope:44s}{v:10.3f}")
        for part, kinds in p["top_ops_ms"].items():
            say(f"    {part} ops: " + "  ".join(
                f"{k} {v:.3f}" for k, v in list(kinds.items())[:5]))
        for name, v, op_name in p["unnamed_ops_ms"]:
            say(f"    {UNNAMED} {name:32s}{v:10.3f}  {op_name}")
    for key in stale(doc):
        say(f"WARNING: {key} holds none of the parts "
            f"{trace_parts.PARTS}: stale compile cache? A scope is debug "
            "info, which the cache's key leaves out; clear .xla_cache/ "
            "(or JAX_COMPILATION_CACHE_DIR) and run again")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--xplane", help="reduce this trace file; run nothing")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--top", type=int, default=8,
                    help="unnamed ops listed a program")
    ap.add_argument("--steps", type=int, default=1,
                    help="with --xplane: steps a run of a fused window")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the tiny rehearsal cells, to debug the flow: a "
                    "CPU trace has no device plane, so the table is empty")
    args = ap.parse_args(argv)
    if args.xplane:
        trace, names = trace_parts.parsed(args.xplane)
        window = trace_reduce.window_of(trace)
        reduced = trace_reduce.reduce_trace(trace)
        rows = trace_parts.op_rows(trace, names, window)
        doc = table(rows, reduced["module_s"], args.steps, args.top)
        show(doc)
        secs = trace_parts.by_part(rows)
        print(json.dumps({
            "programs": doc,
            "seconds_by_part": {k or UNNAMED: v for k, v in secs.items()},
            "unnamed_share": 100.0 * secs.get(None, 0.0) / max(
                sum(secs.values()), 1e-12),
        }))
        return 0
    if not args.workload:
        ap.error("--workload or --xplane")
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
        r, _env, _problems, _facts, _raw = run.measure(
            sut, cfg, cell["traffic"], traffic_dir, args.seed, seconds, True,
        )
    finally:
        sut.close()
    t = time.monotonic()
    rows = trace_parts.rows_of(r)
    parse_s = time.monotonic() - t
    if rows is None:
        print("part_table: the trace file was not found", file=sys.stderr)
        return 4
    steps = max(
        (int(s[3].get("steps", 1)) for s in r.spans_in_trace("decode_window")),
        default=1,
    )
    doc = {
        "workload": cell["name"], "seed": args.seed,
        "window_s": r.trace["window_s"], "busy_s": r.trace["busy_s"],
        "steps_a_fused_run": steps,
        "programs": table(rows, r.trace["module_s"], steps, args.top),
        # the second parse of the trace and the walk of its HLO, which a
        # --trace 1 run pays after its window (outside setup_s)
        "parse_s": parse_s,
    }
    wanted = [
        m for m in run.metrics_for(bench, cell, "per_layer")
        if m["name"] in ("decode_step_device_ms", "decode_mixer_ms_per_step",
                         "decode_ffn_ms_per_step", "decode_head_ms_per_step",
                         "device_unnamed_share")
    ]
    doc["metrics"] = {
        k: v["value"]
        for k, v in run.read_metrics(wanted, "layer_metrics", r).items()
    }
    secs = trace_parts.seconds_by_part(r) or {}
    doc["seconds_by_part"] = {k or UNNAMED: v for k, v in secs.items()}
    total = sum(secs.values())
    doc["unnamed_share"] = 100.0 * secs.get(None, 0.0) / total if total else None
    print(f"{cell['name']} seed {args.seed}: traced {doc['window_s']:.2f} s, "
          f"busy {doc['busy_s']:.2f} s, trace parsed in {parse_s:.1f} s")
    show(doc["programs"])
    print(json.dumps({k: doc[k] for k in (
        "workload", "metrics", "seconds_by_part", "unnamed_share", "parse_s",
    )}))
    out = REPO / "chiprun_out" / "perfbench"
    try:
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{cell['name']}.parts.json").write_text(
            json.dumps(doc, indent=1, default=str)
        )
    except OSError as e:
        print(f"part_table: could not write: {e}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_code)
