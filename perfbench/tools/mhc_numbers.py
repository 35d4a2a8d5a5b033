#!/usr/bin/env python3
"""The numbers check of a configuration whose residual stream is several
lanes (manifold-constrained hyper-connections) round YaRN-scaled latent
attention, at the harness's 192 + 8 positions AND at 2,048 + 64 (past
half of YaRN's original window of 4,096, the expanded form at length,
which ``correctness.py``'s positions end before), with controls that
MUST FAIL the same rule, each the plain reference computing ANOTHER model
(``reference/mhc_mla_moe.py`` ``controls``): the mixing matrix the
identity (``res_identity``), the dynamic half of every coefficient off
(``static``: alpha = 0), one Sinkhorn pass in place of twenty
(``one_pass``), the plain rotary frequencies and scale in place of YaRN's
(``plain_rope``); and the system's weights through float8_e4m3.

    python3 perfbench/tools/mhc_numbers.py --config <configs/*.json> \
        [--seeds 2] [--sequences 8] [--long-prefill 2048] [--long-decode 64]

One ``ModelRunner`` a seed with the file's engine settings and the
benchmark's own ``sut.logits_through_cache``. Needs the chip unless
``--cpu`` (the rehearsal's file: ``--cpu --seeds 1 --sequences 4
--prefill 40 --long-prefill 90 --long-decode 12``). The JSON goes to
``chiprun_out/perfbench/<name>.mhc_numbers.json``; exit 1 unless every
leg came out as it must.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import correctness  # noqa: E402
from perfbench.tools import numbers_readings as nr  # noqa: E402
from perfbench.tools.kda_numbers import judged, scored  # noqa: E402


def one_seed(cfg, reference, seed, args):
    import jax
    import numpy as np

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    settings = dict(cfg["engine"], seed=int(seed) % (2**31 - 1))
    runner = ModelRunner(
        MODEL_CONFIGS[cfg["engine_key"]], EngineConfig(**settings),
        num_pages=2,
    )
    tol = json.loads((HERE / "reference/tolerance.json").read_text())[
        settings["param_dtype"]]
    rng = np.random.default_rng([int(seed), 0x1095])
    ids = rng.integers(
        0, 256, (args.sequences, args.long_prefill + args.long_decode)
    ).astype(np.int32)
    rows = []

    def run(name, n_pre, n_dec, must_pass, controls=(), float8=False):
        positions = scored(n_pre, n_dec, args.tail)
        # the reference first, on the weights as stated: the float8 leg
        # then rounds the runner's weights IN PLACE
        want = np.stack([
            np.asarray(reference.logits_at(
                cfg, runner.params, seq[: n_pre + n_dec], positions,
                controls=controls,
            ), np.float32)
            for seq in ids
        ])
        if float8:
            runner.params = nr.through_float8(runner.params)
        problems, facts = judged(
            cfg, runner, ids, n_pre, n_dec, positions, want, tol
        )
        ok = (not problems) == must_pass
        rows.append({
            "seed": seed, "leg": name, "prefill": n_pre, "decode_steps": n_dec,
            "must_pass": must_pass, "passed": not problems, "as_it_must": ok,
            "rel_err_quantile": facts["rel_err_quantile"],
            "rel_err_max": facts["rel_err_max"], "worst": facts["worst"],
            "positions": facts["positions"],
        })
        print(
            f"seed {seed} {name:12s} {n_pre}+{n_dec}: quantile "
            f"{facts['rel_err_quantile']:.4f} (limit {tol}) max "
            f"{facts['rel_err_max']:.4f} (cap {facts['cap']}) -> "
            f"{'pass' if not problems else 'FAIL'} "
            f"({'as it must' if ok else 'NOT AS IT MUST'})", flush=True,
        )
        return ok

    short = (args.prefill, correctness.N_DECODE)
    ok = run("system", *short, True)
    ok &= run("system", args.long_prefill, args.long_decode, True)
    for control in reference.CONTROLS:
        ok &= run(control, *short, False, controls=(control,))
    ok &= run("float8", *short, False, float8=True)    # last: it rounds
    for leaf in jax.tree_util.tree_leaves((runner.params, runner.cache)):
        if not leaf.is_deleted():
            leaf.delete()
    del runner
    gc.collect()
    return ok, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2147488001)
    ap.add_argument("--sequences", type=int, default=8)
    ap.add_argument("--prefill", type=int, default=correctness.N_PREFILL)
    ap.add_argument("--long-prefill", type=int, default=2048)
    ap.add_argument("--long-decode", type=int, default=64)
    ap.add_argument("--tail", type=int, default=16)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("mhc_numbers: this only runs on the chip (or --cpu)",
              file=sys.stderr)
        return 3
    cfg = json.loads((REPO / args.config).read_text())
    reference = importlib.import_module("perfbench.reference." + cfg["reference"])
    all_ok, rows = True, []
    for i in range(args.seeds):
        ok, got = one_seed(cfg, reference, args.first_seed + i, args)
        all_ok &= ok
        rows += got
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cfg['name']}.mhc_numbers.json").write_text(
        json.dumps(rows, indent=1))
    print("mhc_numbers:", "every leg as it must" if all_ok else "FAILED")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
