#!/usr/bin/env python3
"""The numbers check at the lengths a cell is TIMED at, for a
configuration whose attention SELECTS the keys a query attends to
(``index_topk``): ``correctness.numbers``'s 192 + 8 positions end before
an ``index_topk`` of 2,048 begins to bite, so the harness's own
``correct`` checks the widths, the indexer-free logits and the kernels'
lowering and is blind to the selection, to the index-key pool and to the
gather of the chosen rows. Here the benchmark's own
``sut.logits_through_cache(ids, n_prefill, n_decode)`` runs at each
``--prefill`` length (default 2,040: the decode steps cross
``index_topk``; 6,400: the common prompt; 15,500: the longest) with
``--decode`` steps (16), ``--sequences`` sequences each (4), against the
configuration's float32 reference under the configuration's own routed
rule (``numbers``: quantile, cap; the dtype's tolerance).

TWO controls, each of which the system must FAIL, or the check could not
have seen a selection that was missing or wrong:

- ``dense``: the reference with ``index_topk`` past every position (it
  attends over the whole context);
- ``lowest``: the reference keeping the ``index_topk`` positions of
  SMALLEST index score (the wrong rows).

At a length where no position passes ``index_topk`` the selection is
everything and both controls ARE the reference: such a length has no
control and is reported so. Where the selection drops under a tenth of
the context (2,040 + 16: at most 8 of 2,056 positions, and only at the
last 8 steps) a control differs from the reference by those few
positions and cannot fail: it is run and reported, and REQUIRED to fail
only where the selection drops a tenth of the context or more.

    python3 perfbench/tools/sparse_numbers.py --config <configs/*.json> \
        [--seed N] [--prefill 2040 6400 15500] [--control-sequences 1]

One ``ModelRunner`` with the file's engine settings and the weights the
engine would build for ``--seed``. Needs the chip unless ``--cpu`` (the
rehearsal's files, short lengths). The JSON goes to
``chiprun_out/perfbench/<name>.sparse_numbers.json``; the exit code is 0
when every length passes and every control fails.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import sys
import types
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import correctness  # noqa: E402
from perfbench.tools.long_numbers import judge  # noqa: E402
from perfbench.tools.numbers_readings import stand_in  # noqa: E402

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=2147488001)
    ap.add_argument("--prefill", type=int, nargs="+", default=[2040, 6400, 15500])
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--control-sequences", type=int, default=1)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("sparse_numbers: this only runs on the chip (or --cpu)",
              file=sys.stderr)
        return 3
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = json.loads((REPO / args.config).read_text())
    reference = importlib.import_module("perfbench.reference." + cfg["reference"])
    spec = correctness.routed_spec(cfg)
    topk = int(cfg["index_topk"])
    settings = dict(cfg["engine"], seed=int(args.seed) % (2**31 - 1))
    runner = ModelRunner(
        MODEL_CONFIGS[cfg["engine_key"]], EngineConfig(**settings), num_pages=2,
    )
    sut = stand_in(runner, cfg)
    dtype = settings["param_dtype"]
    tol = float(json.loads(
        (HERE / "reference/tolerance.json").read_text())[dtype])
    # (configuration, reference) of each control
    controls = {
        "dense": (dict(cfg, index_topk=1 << 30), reference),
        "lowest": (cfg, types.SimpleNamespace(
            logits_and_near_ties=functools.partial(
                reference.logits_and_near_ties, select="lowest"))),
    }
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
        passed = not problems
        ok = ok and passed
        row = {
            "seed": args.seed, "n_prefill": n_pre, "n_decode": args.decode,
            "sequences": args.sequences, "passed": passed, **facts,
            "problems": problems[:4], "controls": {},
        }
        said = (
            f"prefill {n_pre} + {args.decode}: "
            f"{'PASS' if passed else 'FAIL'} quantile "
            f"{facts['rel_err_quantile']:.4f} (limit {tol}) max "
            f"{facts['rel_err_max']:.4f} (cap {spec['cap']})"
        )
        total = n_pre + args.decode
        dropped = max(total - topk, 0)
        required = 10 * dropped >= total
        if not dropped:
            said += " | no position passes index_topk: no control"
        else:
            if not required:
                said += (f" | the selection drops at most {dropped} of "
                         f"{total} positions: controls reported, not required")
            for name, (c_cfg, c_reference) in controls.items():
                c_problems, c_facts = judge(
                    c_cfg, c_reference, runner.params,
                    ids[: args.control_sequences], got, positions, tol,
                    dtype, spec)
                caught = bool(c_problems)
                ok = ok and (caught or not required)
                row["controls"][name] = {
                    "failed": caught, "required": required,
                    "sequences": args.control_sequences, **c_facts,
                }
                said += (
                    f" | control {name}: "
                    f"{'fails' if caught else 'PASSES'} quantile "
                    f"{c_facts['rel_err_quantile']:.4f} max "
                    f"{c_facts['rel_err_max']:.4f}"
                )
        rows.append(row)
        print(said, flush=True)
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cfg['name']}.sparse_numbers.json").write_text(
        json.dumps(rows, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
