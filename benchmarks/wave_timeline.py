#!/usr/bin/env python3
"""What the host was doing while the device's queue was empty: one traced
window of a cell, its idle gaps summed by the programs on either side of
them, and the scheduler's spans round the longest gaps printed in order.

    python3 benchmarks/wave_timeline.py --workload <cell> --seed <n> [--gaps 4]

Runs the cell as ``perfbench/run.py --trace 1`` does (same warm-up, window
and tracer; ``perfbench/tools/phase_table.py`` gives the per-phase sums of
the same run). A gap is a stretch of the traced window in which no op runs
on the first chip. Each is named by the program that ended at its start
and the one that began at its end; the timeline of a gap lists every
flight-recorder span that overlaps it, from 30 ms before to 30 ms after,
in milliseconds from the gap's start. The JSON goes to
``chiprun_out/perfbench/<cell>.wave_timeline.json``. Needs the chip.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import run, trace_reduce  # noqa: E402
from perfbench.layer_metrics import idle_unattributed_share  # noqa: E402

MARGIN_S = 0.030
MIN_GAP_S = 0.0005


def programs(raw):
    """The first chip's programs as (start, end, name), by start, on the
    trace's clock."""
    dev = sorted(raw["devices"].items())[0][1]
    return sorted(
        (s, s + d, trace_reduce.module_key(n)) for n, s, d in dev["modules"]
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--gaps", type=int, default=4)
    args = ap.parse_args(argv)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell, cfg = run.load_cell(bench, args.workload)
    seconds = float(args.seconds or bench["run_seconds"])

    from perfbench import sut as sut_mod

    try:
        sut = sut_mod.System(cfg, args.seed, False)
    except sut_mod.NoDevice as e:
        print(str(e), file=sys.stderr)
        return 3
    try:
        r, _env, _problems, _facts, raw = run.measure(
            sut, cfg, cell["traffic"], run.HERE / "traffic", args.seed, seconds, True,
        )
    finally:
        sut.close()

    offset_ns = r.trace_span[0] * 1e9 - r.trace["window_ns"][0]
    mods = programs(raw)
    starts = [ps for ps, _pe, _n in mods]
    by_end = sorted((pe, n) for _ps, pe, n in mods)
    ends = [pe for pe, _n in by_end]
    gaps = [(s, e) for s, e in r.trace["gaps_ns"] if (e - s) * 1e-9 >= MIN_GAP_S]
    between = {}
    named = []
    for s, e in gaps:
        i = bisect.bisect_right(ends, s + 1000.0) - 1
        j = bisect.bisect_left(starts, e - 1000.0)
        before = by_end[i][1] if i >= 0 else "(window start)"
        after = mods[j][2] if j < len(mods) else "(window end)"
        key = f"{before} -> {after}"
        b = between.setdefault(key, [0, 0.0])
        b[0] += 1
        b[1] += (e - s) * 1e-9
        named.append((s, e, before, after))
    spans = sorted((a, b, n, attrs) for n, a, b, attrs in r.spans if b > a)
    timelines = []
    for s, e, before, after in sorted(named, key=lambda g: g[0] - g[1])[: args.gaps]:
        lo, hi = (s + offset_ns) * 1e-9, (e + offset_ns) * 1e-9
        rows = [
            [n, round((a - lo) * 1e3, 3), round((b - lo) * 1e3, 3),
             {k: v for k, v in attrs.items() if k not in ("jobs",)}]
            for a, b, n, attrs in spans
            if b > lo - MARGIN_S and a < hi + MARGIN_S
        ]
        progs = [
            [n, round((ps - s) * 1e-6, 3), round((pe - s) * 1e-6, 3)]
            for ps, pe, n in mods
            if pe > s - MARGIN_S * 1e9 and ps < e + MARGIN_S * 1e9
        ]
        timelines.append({
            "at_s": lo - r.t0, "gap_ms": (e - s) * 1e-6, "before": before,
            "after": after, "device_programs_ms": progs, "host_spans_ms": rows,
        })
    w0, w1 = r.trace["window_ns"]
    t_w0 = (w0 + offset_ns) * 1e-9
    split = idle_unattributed_share.split_by_phase(r)
    # over the whole window: how the wave rows entered their first
    # window (OBSERVABILITY.md "The admission wave"; {} before PR 58)
    joined = {
        k: r.counter_delta("sutro_admit_wave_joined_rows_total", k)
        for k in (r.reg1.get("sutro_admit_wave_joined_rows_total") or {}).get(
            "series", {})
    }
    doc = {
        "workload": cell["name"], "seed": args.seed,
        "output_tokens": r.window_output_tokens(), "window_s": r.seconds,
        "trace_window_s": r.trace["window_s"], "busy_s": r.trace["busy_s"],
        "idle_by_phase_s": dict(sorted(split.items(), key=lambda kv: -kv[1])),
        "idle_between_programs": {
            k: {"gaps": n, "s": secs}
            for k, (n, secs) in sorted(between.items(), key=lambda kv: -kv[1][1])
        },
        "modules": r.trace["module_s"],
        "wave_rows": r.counter_delta("sutro_admit_wave_rows_total"),
        "waves": r.counter_delta("sutro_admit_waves_total"),
        "wave_rows_joined": joined,
        "timelines": timelines,
        # the whole traced window, seconds from its start: the programs of
        # a millisecond and more, the gaps, and every recorder span
        "programs": [
            [n, round((ps - w0) * 1e-9, 6), round((pe - w0) * 1e-9, 6)]
            for ps, pe, n in mods
            if pe - ps >= 1e6 and pe > w0 and ps < w1
        ],
        "gaps": [[round((a - w0) * 1e-9, 6), round((b - w0) * 1e-9, 6)]
                 for a, b in gaps],
        "spans": [
            [n, round(a - t_w0, 6), round(b - t_w0, 6),
             {k: v for k, v in attrs.items() if k in (
                 "cpu_s", "rows", "wave", "wave_rows", "joined_device",
                 "tokens", "batch", "lost")}]
            for a, b, n, attrs in spans
            if b > t_w0 - 0.2 and a < t_w0 + (w1 - w0) * 1e-9 + 0.2
        ],
    }
    print(f"{cell['name']} seed {args.seed}: {doc['output_tokens']} tokens in "
          f"{r.seconds:.1f} s; traced {doc['trace_window_s']:.3f} s, busy "
          f"{doc['busy_s']:.3f} s")
    print(f"waves {doc['waves']:.0f} rows {doc['wave_rows']:.0f} joined "
          + json.dumps(joined))
    print("idle_by_phase_s " + json.dumps(doc["idle_by_phase_s"]))
    print("idle_between_programs (gaps of 0.5 ms and more)")
    for k, v in doc["idle_between_programs"].items():
        print(f"  {v['s']:8.4f} s {v['gaps']:5d}  {k}")
    for t in timelines:
        print(f"gap of {t['gap_ms']:.2f} ms at {t['at_s']:.3f} s: "
              f"{t['before']} -> {t['after']}")
        for n, a, b in t["device_programs_ms"]:
            print(f"    device {a:9.2f} .. {b:9.2f}  {n}")
        for n, a, b, attrs in t["host_spans_ms"]:
            print(f"    host   {a:9.2f} .. {b:9.2f}  {n} {json.dumps(attrs, default=str)}")
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell['name']}.wave_timeline.json").write_text(
        json.dumps(doc, indent=1, default=str)
    )
    return 0


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_code)
