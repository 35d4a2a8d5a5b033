#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the highest of a
few fixed rates at which completions keep up with arrivals and the
backlog at the window's end is no larger than at its middle.

    python3 perfbench/tools/sweep_knee.py --workload <cell> --rates 2,4,6,8 --seconds 20

One process: the engine is built and warmed once, then each rate gets a
window of its own. Prints a JSON line a rate; the traffic file's
``rate_per_s`` is then set by hand to four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE.parent))

from perfbench import run as runner  # noqa: E402
from perfbench import sut as sut_mod  # noqa: E402
from perfbench.stats import percentile  # noqa: E402


def backlog(chats, t: float) -> int:
    """Chats due by ``t`` and not finished by ``t``."""
    return sum(
        1 for c in chats
        if c["due"] <= t and (c["done"] is None or c["done"] > t)
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--part", default="chat-open-loop",
                    help="the traffic file whose rate_per_s is swept")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cell, cfg = runner.load_cell(bench, args.workload)
    sut = sut_mod.System(cfg, args.seed, False)
    try:
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            reading, env, problems, _facts, _raw = runner.measure(
                sut, cfg, cell["traffic"], HERE / "traffic", args.seed + i,
                args.seconds, False, warm=(i == 0),
                overrides={args.part: {"rate_per_s": rate}},
            )
            chats = reading.window_chats()
            done = [c for c in chats if c["done"] is not None and c["error"] is None]
            ttft = [(c["first"] - c["due"]) * 1e3 for c in reading.answered()]
            mid = (reading.t0 + reading.t1) / 2
            pts = env.log.window_rate_points(reading.t0, reading.t1)
            print(json.dumps({
                "rate_per_s": rate, "due": len(chats), "completed": len(done),
                "completed_in_window": sum(1 for c in done if c["done"] <= reading.t1),
                "backlog_mid": backlog(chats, mid),
                "backlog_end": backlog(chats, reading.t1),
                "ttft_p50_ms": percentile(ttft, 50), "ttft_p95_ms": percentile(ttft, 95),
                "out_tokens_per_s": None if pts is None else pts[2] / (pts[1] - pts[0]),
                "problems": problems[:3],
            }), flush=True)
    finally:
        sut.close()
    return 0


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    os._exit(_code)
