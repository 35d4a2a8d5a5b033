#!/usr/bin/env python3
"""What the decode dispatches of one window of a benchmark cell yielded,
by path: what PERF.md's per-path tables are copied from.

    python3 benchmarks/decode_yield_table.py --workload <cell> --seed <n>

Runs the cell as ``perfbench/run.py --trace 0`` does (same warm-up and
window, no profiler) and prints, from the registry's deltas over the
window: per decode path the iterations, the row-steps dispatched, the
tokens committed and the row-steps lost by reason (OBSERVABILITY.md
"What a decode dispatch yields"), and whether row-steps = committed +
lost holds on each path; for a constrained cell the share of the
constrained paths' row-steps (window, verify forward and masked single
step) behind a refused token, the share of the tokens that verify
forwards committed, and the share of unmasked tokens the FSMs accepted
as the scheduler saw it when it chose window or masked step (the
``unmasked_ok`` attr of the window's ``decode_window`` spans: first,
last, least, most, and how many dispatches chose from it) (descriptive
numbers, no benchmark metric), and how the constrained rows' masks
reached the masked steps (``sutro_fsm_mask_rows_total``: the shares
``cached``, ``filtered``, ``packed_here`` of the rows; OBSERVABILITY.md
"The FSM masks travel bit-packed"); the cell's end-to-end metrics; and the
tokens
the accept loops committed beside the tokens the progress stream
counted inside the window (``Reading.window_output_tokens``, the
divisor of the per-token metrics) and the burst rate; and how many admission waves the window resolved
for how many rows (rows a host sync). A builder's tool, outside the
harness: no metric. The JSON goes to
``chiprun_out/perfbench/<cell>.seed<n>.yield.json``.

Needs the chip, like run.py; ``--cpu-rehearsal`` runs the tiny cells
and prints no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import run  # noqa: E402
from perfbench.layer_metrics import decode_burst_tokens_per_s  # noqa: E402
from perfbench.layer_metrics.decode_row_steps_kept_share import (  # noqa: E402
    COMMITTED, LOST, STEPS,
)

ITERATIONS = "sutro_sched_iterations_total"


def deltas(r, name):
    keys = set()
    for reg in (r.reg0, r.reg1):
        keys |= set((reg.get(name) or {}).get("series", {}))
    return {k: r.counter_delta(name, k) for k in sorted(keys)}


def admission(r):
    waves = r.counter_delta("sutro_admit_waves_total")
    rows = r.counter_delta("sutro_admit_wave_rows_total")
    # how the rows entered their first window (nothing on a tree before
    # PR 58): by the token on the device, or armed on the host first
    joined = deltas(r, "sutro_admit_wave_joined_rows_total")
    return {"waves": waves, "rows": rows,
            "rows_a_sync": rows / waves if waves else None,
            "joined_device": joined.get("device"),
            "joined_host": joined.get("host"),
            "joined_device_share": share(joined.get("device", 0.0), rows)
            if joined and rows else None,
            # windows asked for ahead of one in flight: sent, or held
            # back and why (nothing on a tree before PR 58)
            "ahead": deltas(r, "sutro_decode_ahead_windows_total")}


def mask_rows(r):
    """How the constrained rows' masks reached the mask assembly: rows
    and shares (%) by path over the window; None where no masked row was
    assembled (or the tree has no such counter)."""
    rows = deltas(r, "sutro_fsm_mask_rows_total")
    total = sum(rows.values())
    if not total:
        return None
    return {"rows": int(total),
            **{path: share(n, total) for path, n in rows.items()}}


def table(r):
    """{path: {iterations, row_steps, committed, lost: {reason: n},
    adds_up}} over the window."""
    out = {}
    for path, n in deltas(r, ITERATIONS).items():
        out[path] = {"iterations": int(n), "row_steps": 0, "committed": 0,
                     "lost": {}}
    for name, key in ((STEPS, "row_steps"), (COMMITTED, "committed")):
        for path, n in deltas(r, name).items():
            out.setdefault(path, {"iterations": 0, "row_steps": 0,
                                  "committed": 0, "lost": {}})[key] = int(n)
    for k, n in deltas(r, LOST).items():
        path, _, reason = k.partition(",")
        if n:
            out[path]["lost"][reason] = int(n)
    for row in out.values():
        row["adds_up"] = row["row_steps"] == row["committed"] + sum(
            row["lost"].values()
        )
    return out


def share(part, whole):
    return 100.0 * part / whole if whole else None


def unmasked_ok(r):
    """What the scheduler chose window or masked step from, dispatch by
    dispatch over the window: the ``unmasked_ok`` attr of the
    ``decode_window`` spans (None where the program has none)."""
    seen = [
        (t0, attrs["unmasked_ok"])
        for name, t0, _t1, attrs in (r.window_spans or r.spans)
        if name == "decode_window" and r.t0 <= t0 < r.t1
        and "unmasked_ok" in (attrs or {})
    ]
    if not seen:
        return None
    vals = [v for _t, v in sorted(seen)]
    return {"dispatches": len(vals), "first": vals[0], "last": vals[-1],
            "min": min(vals), "max": max(vals)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    traffic_dir = REPO / "perfbench" / "traffic"
    if args.cpu_rehearsal:
        cells = json.loads(
            (REPO / "perfbench/rehearsal/cells.json").read_text()
        )
        bench = dict(bench, configs=cells["configs"],
                     workloads=cells["workloads"])
        traffic_dir = REPO / "perfbench" / "rehearsal" / "traffic"
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
            sut, cfg, cell["traffic"], traffic_dir, args.seed, seconds, False,
        )
        e2e = run.read_metrics(
            run.metrics_for(bench, cell, "end_to_end"), "e2e_metrics", r
        )
    finally:
        sut.close()

    by_path = table(r)
    committed = sum(p["committed"] for p in by_path.values())
    steps = sum(p["row_steps"] for p in by_path.values())
    # what a constrained batch takes: the speculative window, the verify
    # forward, and the masked single step where windows are being refused
    estimate = unmasked_ok(r)
    spec = [
        by_path[p] for p in ("window", "fastforward") if p in by_path
    ]
    if spec and "single" in by_path:
        spec.append(by_path["single"])
    spec_steps = sum(p["row_steps"] for p in spec)
    doc = {
        "workload": cell["name"], "seed": args.seed, "window_s": r.seconds,
        "by_path": by_path, "row_steps": steps, "committed": committed,
        "kept_share": share(committed, steps),
        # of the constrained paths' row-steps, those behind a token the
        # FSM refused: what a mask inside the window's steps would win
        "constrained_rejected_share": share(
            sum(p["lost"].get("rejected", 0) for p in spec), spec_steps
        ),
        # of the tokens committed, those a verify forward committed: how
        # much of the job rides on jumps (descriptive: no better way)
        "fastforward_committed_share": share(
            by_path.get("fastforward", {}).get("committed", 0), committed
        ),
        "single_committed_share": share(
            by_path.get("single", {}).get("committed", 0), committed
        ),
        # the share of unmasked tokens the FSMs accepted, as the
        # scheduler's rule read it at each dispatch of the window
        "unmasked_ok": estimate,
        # of the constrained rows the mask assembly wrote, the share a
        # kept packed array served, the share a budget filtered, the
        # share packed there
        "fsm_mask_rows": mask_rows(r),
        # the divisor of fsm_host_us_per_token and the burst rate: the
        # progress stream's ticks clipped to the window
        "window_output_tokens": r.window_output_tokens(),
        "dispatch_rows": r.counter_delta("sutro_sched_dispatch_rows_total"),
        # how often admission waited for the device, and for how many
        # rows (OBSERVABILITY.md "The admission wave"; a tree without
        # the counters reads 0 and no ratio)
        "admission": admission(r),
        # tokens over seconds between the window's first and last
        # progress update: the pace that differs by seed in the
        # classify cell, beside the verify forwards' share of it
        "decode_burst_tokens_per_s": decode_burst_tokens_per_s.read(r),
        "end_to_end": e2e,
    }
    tag = run.REHEARSAL_TAG if args.cpu_rehearsal else ""
    print(tag + f"{cell['name']} seed {args.seed}: window {r.seconds:.1f} s")
    print(tag + f"{'path':12s}{'iterations':>11s}{'row_steps':>11s}"
          f"{'committed':>11s}{'kept %':>8s}  lost by reason")
    for path, p in sorted(by_path.items()):
        kept = (f"{100.0 * p['committed'] / p['row_steps']:.2f}"
                if p["row_steps"] else "")
        ok = "" if p["adds_up"] else "  DOES NOT ADD UP"
        print(tag + f"{path:12s}{p['iterations']:11d}{p['row_steps']:11d}"
              f"{p['committed']:11d}{kept:>8s}  {json.dumps(p['lost'])}{ok}")
    print(tag + json.dumps({k: v for k, v in doc.items() if k != "by_path"}))
    out = REPO / "chiprun_out" / "perfbench"
    try:
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{cell['name']}.seed{args.seed}.yield.json").write_text(
            json.dumps(doc, indent=1, default=str)
        )
    except OSError as e:
        print(f"decode_yield_table: could not write: {e}", file=sys.stderr)
    return 0 if all(p["adds_up"] for p in by_path.values()) else 1


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_code)
