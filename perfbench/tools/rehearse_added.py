"""Run a CPU rehearsal cell that a PR added beside ``rehearsal/cells.json``.

``run.py --cpu-rehearsal`` reads ``rehearsal/cells.json`` alone, and a PR
that changes the program may add files to the benchmark but edit none. So
such a PR keeps its rehearsal entries in a file of its own (``configs`` and
``workloads``, as ``cells.json`` has them), and this tool copies
``BENCHMARK.json`` and ``perfbench/`` into a temporary directory, appends
the entries to the copy's ``cells.json`` and runs the copy's ``run.py
--cpu-rehearsal`` with the remaining flags. The program (``sutro_tpu``) is
this checkout's; the exit code and the output are ``run.py``'s.

    python3 perfbench/tools/rehearse_added.py \
        --cells perfbench/rehearsal/cells-lfm2.json \
        --workload tiny-lfm2.generate-jobs --seed 7 --seconds 8 --trace 1
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, type=Path,
                    help="the file of added entries (configs, workloads)")
    args, run_flags = ap.parse_known_args(argv)
    added = json.loads(args.cells.read_text())
    with tempfile.TemporaryDirectory(prefix="perfbench-rehearsal-") as tmp:
        root = Path(tmp)
        shutil.copy(REPO / "BENCHMARK.json", root)
        shutil.copytree(REPO / "perfbench", root / "perfbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cells_file = root / "perfbench" / "rehearsal" / "cells.json"
        cells = json.loads(cells_file.read_text())
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            if added.get(key):
                cells[key] = cells.get(key, []) + added[key]
        cells_file.write_text(json.dumps(cells, indent=1))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(REPO)] + [p for p in [env.get("PYTHONPATH")] if p]
        )
        return subprocess.run(
            [sys.executable, str(root / "perfbench" / "run.py"),
             "--cpu-rehearsal", *run_flags],
            env=env, cwd=root,
        ).returncode


if __name__ == "__main__":
    sys.exit(main())
