#!/usr/bin/env python3
"""The numbers check at the lengths a cell is TIMED at, for a
configuration whose mechanisms ``correctness.numbers`` cannot reach: its
192 + 8 positions end before a window of 1,024 begins, so the harness's
own ``correct`` is blind to the window, to the paged kernel's first
page and to the window pool's map. Here the benchmark's own
``sut.logits_through_cache(ids, n_prefill, n_decode)`` runs at each
``--prefill`` length (default 1,500, 2,040 and 3,400: past the window,
a page boundary crossed inside the decode steps, and the longest timed
prompt) with ``--decode`` steps (16), ``--sequences`` sequences each
(8), against the configuration's float32 reference under the
configuration's own routed rule (``numbers``: quantile, cap; the
dtype's tolerance).

The control takes the window away from the REFERENCE (``sliding_window``
past every position: the window layers attend over the whole context):
the system, which keeps the window, must then FAIL the rule, or the
check could not have seen a window that was missing or misplaced.

    python3 perfbench/tools/long_numbers.py --config <configs/*.json> \
        [--seed N] [--prefill 1500 2040 3400] [--control-sequences 2]

One ``ModelRunner`` with the file's engine settings and the weights the
engine would build for ``--seed``. Needs the chip unless ``--cpu`` (the
rehearsal's files, short lengths). The JSON goes to
``chiprun_out/perfbench/<name>.long_numbers.json``; the exit code is 0
when every length passes and every control fails.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import correctness  # noqa: E402
from perfbench.tools.numbers_readings import stand_in  # noqa: E402


def judge(cfg, reference, params, ids, got, positions, tol, dtype, spec):
    import numpy as np

    want = np.stack([
        np.asarray(reference.logits_and_near_ties(cfg, params, seq, positions)[0],
                   np.float32)
        for seq in ids
    ])
    errs = correctness.position_errors(got[: len(ids)], want)
    where = [f"sequence {s} position {p}"
             for s in range(len(ids)) for p in positions]
    problems, facts = correctness.routed_rule(errs, tol, dtype, spec, where)
    return problems, {
        k: facts[k] for k in ("positions", "rel_err_quantile", "rel_err_max",
                              "worst", "share_over_tolerance")
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=2147488001)
    ap.add_argument("--prefill", type=int, nargs="+", default=[1500, 2040, 3400])
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sequences", type=int, default=8)
    ap.add_argument("--control-sequences", type=int, default=2)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("long_numbers: this only runs on the chip (or --cpu)",
              file=sys.stderr)
        return 3
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = json.loads((REPO / args.config).read_text())
    reference = importlib.import_module("perfbench.reference." + cfg["reference"])
    spec = correctness.routed_spec(cfg)
    settings = dict(cfg["engine"], seed=int(args.seed) % (2**31 - 1))
    runner = ModelRunner(
        MODEL_CONFIGS[cfg["engine_key"]], EngineConfig(**settings), num_pages=2,
    )
    sut = stand_in(runner, cfg)
    dtype = settings["param_dtype"]
    tol = float(json.loads(
        (HERE / "reference/tolerance.json").read_text())[dtype])
    no_window = dict(cfg, sliding_window=1 << 30)
    rows, ok = [], True
    for n_pre in args.prefill:
        rng = np.random.default_rng([int(args.seed), n_pre, 0x1095])
        ids = rng.integers(
            0, 256, (args.sequences, n_pre + args.decode)
        ).astype(np.int32)
        positions = list(range(n_pre - 1, n_pre + args.decode))
        got = sut.logits_through_cache(ids, n_pre, args.decode)
        problems, facts = judge(
            cfg, reference, runner.params, ids, got, positions, tol, dtype, spec)
        c_problems, c_facts = judge(
            no_window, reference, runner.params,
            ids[: args.control_sequences], got, positions, tol, dtype, spec)
        passed, caught = not problems, bool(c_problems)
        ok = ok and passed and caught
        rows.append({
            "seed": args.seed, "n_prefill": n_pre, "n_decode": args.decode,
            "sequences": args.sequences, "passed": passed, **facts,
            "problems": problems[:4],
            "control": {"window": "taken from the reference", "failed": caught,
                        "sequences": args.control_sequences, **c_facts},
        })
        print(
            f"prefill {n_pre} + {args.decode}: "
            f"{'PASS' if passed else 'FAIL'} quantile "
            f"{facts['rel_err_quantile']:.4f} (limit {tol}) max "
            f"{facts['rel_err_max']:.4f} (cap {spec['cap']}) | control "
            f"without the window: {'fails' if caught else 'PASSES'} quantile "
            f"{c_facts['rel_err_quantile']:.4f} max {c_facts['rel_err_max']:.4f}",
            flush=True,
        )
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cfg['name']}.long_numbers.json").write_text(json.dumps(rows, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
