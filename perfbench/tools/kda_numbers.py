#!/usr/bin/env python3
"""The numbers check of a delta-rule (KDA) configuration past what the
harness's 192 + 8 positions show, with the controls that must fail.

``correctness.numbers`` prefills 192 tokens and takes 8 decode steps: 8
commits of the bf16 state. A row of the timed cell takes hundreds. So,
on weights made from each seed, through ``sut.logits_through_cache`` and
the configuration's own routed rule (``numbers``):

- the system at 192 + 8 and at 192 + ``--long`` (384) decode steps, each
  step a commit of the state in its stored dtype, scored at the last
  prefill position, the first 8 steps and ``--tail`` (24) positions
  spread to the end: both must pass;
- ``no_decay`` and ``no_delta``: the same system logits against the
  reference computing ANOTHER model (``g = 0``; no ``- beta k k^T``
  term): each must FAIL, or the check could not tell the mechanism;
- ``float8``: the system on weights rounded through float8_e4m3 against
  the reference on the weights as stated: must FAIL.

    python3 perfbench/tools/kda_numbers.py --config <configs/*.json> \
        [--seeds 2] [--sequences 8] [--long 384] [--cpu]

Exit code 0 only if every leg reads as it must. The JSON goes to
``chiprun_out/perfbench/<name>.kda_numbers.json``.
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


def scored(n_prefill: int, n_decode: int, tail: int):
    """Positions scored: the last prefilled, the first 8 steps, and
    ``tail`` more spread to the last step (indices into the system's
    ``[1 + n_decode]`` outputs are position - (n_prefill - 1))."""
    import numpy as np

    head = list(range(n_prefill - 1, n_prefill + min(8, n_decode)))
    if n_decode <= 8:
        return head
    rest = np.linspace(n_prefill + 8, n_prefill + n_decode - 1, tail)
    return sorted(set(head) | {int(p) for p in rest})


def wanted(cfg, reference, params, ids, n, positions, variant=None):
    """The reference's logits at ``positions`` of each sequence's first
    ``n`` tokens (``variant``: another model)."""
    import numpy as np

    kw = {"variant": variant} if variant else {}
    return np.stack([
        np.asarray(reference.logits_at(cfg, params, seq[:n], positions, **kw),
                   np.float32)
        for seq in ids
    ])


def judged(cfg, runner, ids, n_pre, n_dec, positions, want, tol):
    """(problems, facts) of the system's logits through its cache against
    ``want`` by the configuration's routed rule."""
    got = nr.stand_in(runner, cfg).logits_through_cache(ids, n_pre, n_dec)
    got = got[:, [p - (n_pre - 1) for p in positions]]
    where = [f"sequence {s} position {p}"
             for s in range(len(ids)) for p in positions]
    return correctness.routed_rule(
        correctness.position_errors(got, want), tol,
        str(runner.ecfg.param_dtype), correctness.routed_spec(cfg), where,
    )


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
    n_pre = args.prefill
    rng = np.random.default_rng([int(seed), 0x1095])
    ids = rng.integers(
        0, 256, (args.sequences, n_pre + args.long)
    ).astype(np.int32)
    rows = []

    def run(name, n_dec, must_pass, variant=None, float8=False):
        positions = scored(n_pre, n_dec, args.tail)
        # the reference first, on the weights as stated: the float8 leg
        # then rounds the runner's weights IN PLACE
        want = wanted(cfg, reference, runner.params, ids, n_pre + n_dec,
                      positions, variant)
        if float8:
            runner.params = nr.through_float8(runner.params)
        problems, facts = judged(
            cfg, runner, ids, n_pre, n_dec, positions, want, tol
        )
        ok = (not problems) == must_pass
        rows.append({
            "seed": seed, "leg": name, "decode_steps": n_dec,
            "must_pass": must_pass, "passed": not problems, "as_it_must": ok,
            "rel_err_quantile": facts["rel_err_quantile"],
            "rel_err_max": facts["rel_err_max"], "worst": facts["worst"],
            "positions": facts["positions"],
        })
        print(
            f"seed {seed} {name:9s} {n_pre}+{n_dec}: quantile "
            f"{facts['rel_err_quantile']:.4f} (limit {tol}) max "
            f"{facts['rel_err_max']:.4f} (cap {facts['cap']}) -> "
            f"{'pass' if not problems else 'FAIL'} "
            f"({'as it must' if ok else 'NOT AS IT MUST'})", flush=True,
        )
        return ok

    short = correctness.N_DECODE
    ok = run("system", short, True)
    ok &= run("system", args.long, True)
    for variant in ("no_decay", "no_delta"):
        ok &= run(variant, short, False, variant=variant)
    ok &= run("float8", short, False, float8=True)    # last: it rounds
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
    ap.add_argument("--first-seed", type=int, default=2147487001)
    ap.add_argument("--sequences", type=int, default=8)
    ap.add_argument("--prefill", type=int, default=correctness.N_PREFILL)
    ap.add_argument("--long", type=int, default=384)
    ap.add_argument("--tail", type=int, default=24)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("kda_numbers: this only runs on the chip (or --cpu)",
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
    (out / f"{cfg['name']}.kda_numbers.json").write_text(
        json.dumps(rows, indent=1))
    print("kda_numbers:", "every leg as it must" if all_ok else "FAILED")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
