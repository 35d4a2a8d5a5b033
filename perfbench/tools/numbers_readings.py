#!/usr/bin/env python3
"""The readings behind a routed configuration's ``numbers`` key: for
each seed, the errors of ``correctness.numbers``'s positions (the
runner's logits through its paged cache against the configuration's
float32 reference, on weights made from the seed) as quantiles, the
largest, the share over the dtype's tolerance, and the median by the
reference's count of near ties; and the same for a control whose weights
went through float8_e4m3 (the precision below bf16), which has to read
as not correct.

    python3 perfbench/tools/numbers_readings.py --config <configs/*.json> \
        --seeds 12 --control-seeds 3 [--first-seed N]

No engine, no traffic: one ``ModelRunner`` a seed with the file's engine
settings (the weights the engine would build for that ``--seed``), and
the benchmark's own ``sut.logits_through_cache``. Needs the chip unless
``--cpu`` (the rehearsal's files). The JSON goes to
``chiprun_out/perfbench/<name>.numbers_readings.json``.
"""

from __future__ import annotations

import argparse
import gc
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

QUANTILES = (0.05, 0.1, 0.25, 0.5, 0.75, 0.99)


def through_float8(params):
    """Every matrix rounded through float8_e4m3 and back, IN PLACE (the
    argument is donated: two copies of the weights do not fit a chip)."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim >= 2 else x

    # XLA folds a convert down and back up away unless told not to
    # keep the excess precision (on the TPU the control then IS the
    # system, to the digit: my chip run, PR 28)
    fn = jax.jit(lambda p: jax.tree_util.tree_map(rounded, p), donate_argnums=0)
    return fn.lower(params).compile(
        compiler_options={"xla_allow_excess_precision": False}
    )(params)


def stand_in(runner, cfg):
    from perfbench.sut import System

    sut = object.__new__(System)
    sut.ecfg, sut.engine_key = runner.ecfg, cfg["engine_key"]
    sut.engine = types.SimpleNamespace(
        _runner_cache={cfg["engine_key"]: (runner, None)}
    )
    return sut


def one_seed(cfg, reference, seed: int, control: bool, sequences=None):
    import numpy as np

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    settings = dict(cfg["engine"], seed=int(seed) % (2**31 - 1))
    runner = ModelRunner(
        MODEL_CONFIGS[cfg["engine_key"]], EngineConfig(**settings),
        num_pages=2,
    )
    spec = correctness.routed_spec(cfg)
    rng = np.random.default_rng([int(seed), 0x1095])
    n_pre, n_dec = correctness.N_PREFILL, correctness.N_DECODE
    positions = list(range(n_pre - 1, n_pre + n_dec))
    ids = rng.integers(
        0, 256, (sequences or spec["sequences"], n_pre + n_dec)
    ).astype(np.int32)
    want, ties = [], []
    for seq in ids:   # the reference first, on the weights as stated
        w, t = reference.logits_and_near_ties(cfg, runner.params, seq, positions)
        want.append(np.asarray(w, np.float32))
        ties.append(np.asarray(t))
    if control:
        runner.params = through_float8(runner.params)
    stated = runner.params
    got = stand_in(runner, cfg).logits_through_cache(ids, n_pre, n_dec)
    errs = correctness.position_errors(got, np.stack(want)).ravel()
    ties = np.stack(ties).ravel()
    tol = json.loads((HERE / "reference/tolerance.json").read_text())[
        settings["param_dtype"]]
    out = {
        "seed": seed, "control": control, "positions": int(errs.size),
        "quantiles": {str(q): float(np.quantile(errs, q)) for q in QUANTILES},
        "max": float(errs.max()), "share_over_tolerance": float(np.mean(errs > tol)),
        "near_ties_mean": float(ties.mean()),
        "median_by_near_ties": {
            str(n): [int((ties == n).sum()), float(np.median(errs[ties == n]))]
            for n in sorted(set(ties.tolist()))
        },
        "errors": [round(float(e), 5) for e in errs],   # sequence-major
    }
    per = len(positions)
    out["by_sequences"] = {
        str(n): [float(np.quantile(errs[: n * per], 0.1)),
                 float(np.quantile(errs[: n * per], 0.25)),
                 float(errs[: n * per].max())]
        for n in (4, 8, 16, 32, 64) if n * per <= errs.size
    }
    # the jitted methods' caches keep every runner alive (it is their
    # static argument), so the next seed's weights fit only if this
    # one's buffers are given back by hand (the second small runner's
    # pool is given back by ``sut.logits_through_cache`` itself)
    import jax

    for leaf in jax.tree_util.tree_leaves(
        (stated, runner.params, runner.cache)
    ):
        if not leaf.is_deleted():
            leaf.delete()
    del runner, stated, got
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147486001)
    ap.add_argument("--sequences", type=int, default=None,
                    help="instead of the file's numbers.sequences")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("numbers_readings: this only runs on the chip (or --cpu)",
              file=sys.stderr)
        return 3
    cfg = json.loads((REPO / args.config).read_text())
    reference = importlib.import_module("perfbench.reference." + cfg["reference"])
    rows = []
    plan = [(args.first_seed + i, False) for i in range(args.seeds)] + [
        (args.first_seed + i, True) for i in range(args.control_seeds)]
    for seed, control in plan:
        row = one_seed(cfg, reference, seed, control, args.sequences)
        rows.append(row)
        q = row["quantiles"]
        print(
            f"{'control' if control else 'system '} seed {seed}: "
            + " ".join(f"q{k} {v:.4f}" for k, v in q.items())
            + f" max {row['max']:.4f} over {row['share_over_tolerance']:.2f}"
            + f" near ties {row['near_ties_mean']:.2f} by ties "
            + " ".join(f"{n}:{c}@{m:.3f}" for n, (c, m) in
                       row["median_by_near_ties"].items())
            + " | first n sequences (q0.1, q0.25, max): "
            + " ".join(f"{n}:{a:.3f},{b:.3f},{c:.3f}" for n, (a, b, c) in
                       row["by_sequences"].items()),
            flush=True,
        )
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cfg['name']}.numbers_readings.json").write_text(
        json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
