#!/usr/bin/env python3
"""The controls of this model's OWN mechanisms, which
``tools/long_numbers.py``'s control (the window taken from the reference)
does not reach: the benchmark's ``sut.logits_through_cache(ids,
n_prefill, n_decode)`` at each ``--prefill`` length against
``reference/laguna_moe.py`` under the configuration's routed rule, which
has to PASS, and against the reference's ``variant``s, each of which has
to FAIL: the full layers' rotary part over the whole head
(``rotary_whole_head``), the gate a head left out (``no_head_gate``), the
shared expert's gate left out (``no_shared_gate``). A system that took a
control's form would pass it and fail the reference.

    python3 perfbench/tools/laguna_numbers.py --config <configs/*.json> \
        [--seed N] [--prefill 600 2040] [--variants rotary_whole_head ...]

One ``ModelRunner`` with the file's engine settings and the weights the
engine would build for ``--seed``. Needs the chip unless ``--cpu`` (the
rehearsal's file, short lengths). The JSON goes to
``chiprun_out/perfbench/<name>.laguna_numbers.json``; the exit code is 0
when every length passes and every control fails.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import types
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import correctness  # noqa: E402
from perfbench.reference import laguna_moe  # noqa: E402
from perfbench.tools.long_numbers import judge  # noqa: E402
from perfbench.tools.numbers_readings import stand_in  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=2147489101)
    ap.add_argument("--prefill", type=int, nargs="+", default=[600, 2040])
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--control-sequences", type=int, default=2)
    ap.add_argument("--variants", nargs="+", default=list(laguna_moe.VARIANTS[1:]))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("laguna_numbers: this only runs on the chip (or --cpu)",
              file=sys.stderr)
        return 3
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = json.loads((REPO / args.config).read_text())
    spec = correctness.routed_spec(cfg)
    settings = dict(cfg["engine"], seed=int(args.seed) % (2**31 - 1))
    runner = ModelRunner(
        MODEL_CONFIGS[cfg["engine_key"]], EngineConfig(**settings), num_pages=2,
    )
    sut = stand_in(runner, cfg)
    dtype = settings["param_dtype"]
    tol = float(json.loads(
        (HERE / "reference/tolerance.json").read_text())[dtype])
    rows, ok = [], True
    for n_pre in args.prefill:
        rng = np.random.default_rng([int(args.seed), n_pre, 0x1A61])
        ids = rng.integers(
            0, 256, (args.sequences, n_pre + args.decode)
        ).astype(np.int32)
        positions = list(range(n_pre - 1, n_pre + args.decode))
        got = sut.logits_through_cache(ids, n_pre, args.decode)
        problems, facts = judge(
            cfg, laguna_moe, runner.params, ids, got, positions, tol, dtype,
            spec)
        row = {"seed": args.seed, "n_prefill": n_pre, "passed": not problems,
               **facts, "problems": problems[:4], "controls": {}}
        ok = ok and not problems
        line = (f"prefill {n_pre} + {args.decode}: "
                f"{'PASS' if not problems else 'FAIL'} quantile "
                f"{facts['rel_err_quantile']:.4f} (limit {tol}) max "
                f"{facts['rel_err_max']:.4f} (cap {spec['cap']})")
        for variant in args.variants:
            control = types.SimpleNamespace(
                logits_and_near_ties=functools.partial(
                    laguna_moe.logits_and_near_ties, variant=variant))
            c_problems, c_facts = judge(
                cfg, control, runner.params, ids[: args.control_sequences],
                got, positions, tol, dtype, spec)
            row["controls"][variant] = {"failed": bool(c_problems), **c_facts}
            ok = ok and bool(c_problems)
            line += (f" | {variant}: "
                     f"{'fails' if c_problems else 'PASSES'} quantile "
                     f"{c_facts['rel_err_quantile']:.4f}")
        rows.append(row)
        print(line, flush=True)
    # which path each attention kernel took, a head count (the program's
    # own trace-time counts, where it has them)
    from sutro_tpu.ops import lowering

    heads = getattr(lowering, "kernel_heads_counts", dict)()
    print("kernel_heads", json.dumps(heads), flush=True)
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cfg['name']}.laguna_numbers.json").write_text(
        json.dumps({"lengths": rows, "kernel_heads": heads}, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
