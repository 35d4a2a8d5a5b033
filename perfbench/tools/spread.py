#!/usr/bin/env python3
"""Spread of a cell's runs, as the contract measures it: for each metric
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, per set
of runs, and the bound that five times the widest spread would give.

Reads a log in which each run is preceded by a marker line

    ### set=<a|b> cell=<name> seed=<n>

and ends with the run's result line (what ``chiprun -- bash -c 'for ...;
do echo "### set=a cell=$c seed=$s"; python3 perfbench/run.py ...; done'``
leaves). The first run of a log is cold (it compiles): ``--skip-first``
leaves it out of ``setup_s``.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
from collections import defaultdict

MARK = re.compile(r"^### set=(\S+) cell=(\S+) seed=(\d+)")


def spread(values) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("logs", nargs="+")
    args = ap.parse_args(argv)
    runs = defaultdict(lambda: defaultdict(list))   # cell -> set -> [result]
    for path in args.logs:
        current = None
        for line in open(path, errors="replace"):
            m = MARK.match(line)
            if m:
                current = (m.group(2), m.group(1), int(m.group(3)))
            elif line.startswith('{"correct"') and current:
                res = json.loads(line)
                res["seed"] = current[2]
                runs[current[0]][current[1]].append(res)
                current = None
    for cell, sets in runs.items():
        print(f"== {cell}")
        widest = {}
        for name, results in sorted(sets.items()):
            ok = sum(1 for r in results if r["correct"] and not r["failed"])
            print(f"  set {name}: {len(results)} runs, {ok} correct with none failed")
            metrics = sorted({k for r in results for k in r["metrics"]})
            for k in metrics:
                vals = [r["metrics"][k]["value"] for r in results if k in r["metrics"]]
                if len(vals) < 2:
                    continue
                s = spread(vals)
                widest[k] = max(widest.get(k, 0.0), s)
                print(f"    {k}: median {statistics.median(vals):.6g} "
                      f"spread {100 * s:.2f}%  values "
                      + " ".join(f"{v:.5g}" for v in vals))
        for k, s in sorted(widest.items()):
            print(f"  widest {k}: {100 * s:.2f}%  -> 5x = {500 * s:.1f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
