#!/usr/bin/env python3
"""The numbers check of a configuration that generates by diffusion over
blocks, at the harness's 192 + 8 positions, at 1,984 + 64 (the whole
context of the cell's engine: 31 pages of past under the block kernel,
the flash kernel's block mask over 15 diagonal tiles) AND at 8 + 8 (a
block's own keys a third to a half of what its queries see, where the
mask INSIDE a block decides the logits: over 192 near-uniform keys three
keys more or fewer move a bf16 model's logits by 2-3 % of the largest,
inside the tolerance: my chip run, PR 57), on GIVEN tokens
and on a last block that holds 1, 2, 3 and 4 mask tokens (the denoising
forward as the timed path feeds it), with controls that MUST FAIL the
same rule, each the plain reference computing another model
(``reference/sdar_moe.py`` ``control_logits``): the causal mask in the
block mask's place in the prefill (``causal_prefill``) and in the block
forwards (``causal_block``), a block's K/V committed from the forward
BEFORE its last transfer (``stale_commit``: the cache holds a mask
token's keys), the logits scored one position early (``shifted``); and
the system's weights through float8_e4m3.

    python3 perfbench/tools/bd_numbers.py --config <configs/*.json> \\
        [--seeds 2] [--sequences 8] [--long-prefill 1984] [--long-decode 64]

One ``ModelRunner`` a seed with the file's engine settings and the
benchmark's own ``sut.logits_through_cache``, which asks the runner's
``forced_logits``. Needs the chip unless ``--cpu`` (the rehearsal's file:
``--cpu --seeds 1 --sequences 4 --prefill 40 --long-prefill 88
--long-decode 12``). The JSON goes to
``chiprun_out/perfbench/<name>.bd_numbers.json``; exit 1 unless every
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
    Bk, MASK = int(cfg["block_length"]), int(cfg["mask_token_id"])
    rng = np.random.default_rng([int(seed), 0x1095])
    ids = rng.integers(
        0, 256, (args.sequences, args.long_prefill + args.long_decode)
    ).astype(np.int32)
    rows = []

    def run(name, n_pre, n_dec, must_pass, *, control=None, masks=0,
            float8=False, positions=None):
        seqs = ids[:, : n_pre + n_dec].copy()
        if masks:
            # the LAST block as a denoising forward meets it: ``masks``
            # of its positions open, at places drawn a sequence
            for seq in seqs:
                seq[n_pre + n_dec - Bk + rng.permutation(Bk)[:masks]] = MASK
        positions = positions or scored(n_pre, n_dec, args.tail)
        # the reference first, on the weights as stated: the float8 leg
        # then rounds the runner's weights IN PLACE
        if control is None:
            want = [reference.logits_at(cfg, runner.params, s, positions)
                    for s in seqs]
        else:
            want = [reference.control_logits(
                cfg, runner.params, s, n_pre, positions, control
            ) for s in seqs]
        want = np.stack([np.asarray(w, np.float32) for w in want])
        if float8:
            runner.params = nr.through_float8(runner.params)
        problems, facts = judged(
            cfg, runner, seqs, n_pre, n_dec, positions, want, tol
        )
        ok = (not problems) == must_pass
        rows.append({
            "seed": seed, "leg": name, "prefill": n_pre, "decode": n_dec,
            "must_pass": must_pass, "passed": not problems, "as_it_must": ok,
            "rel_err_quantile": facts["rel_err_quantile"],
            "rel_err_cap_quantile": facts["rel_err_cap_quantile"],
            "rel_err_max": facts["rel_err_max"], "worst": facts["worst"],
            "positions": facts["positions"],
        })
        print(
            f"seed {seed} {name:15s} {n_pre}+{n_dec}: quantile "
            f"{facts['rel_err_quantile']:.4f} (limit {tol}) cap quantile "
            f"{facts['rel_err_cap_quantile']:.4f} (cap {facts['cap']}) max "
            f"{facts['rel_err_max']:.4f} -> "
            f"{'pass' if not problems else 'FAIL'} "
            f"({'as it must' if ok else 'NOT AS IT MUST'})", flush=True,
        )
        return ok

    n_pre, n_dec = args.prefill, correctness.N_DECODE
    short = args.short_prefill
    decoded = list(range(short, short + n_dec))
    last = decoded[-Bk:]
    ok = run("system", n_pre, n_dec, True)
    ok &= run("system", args.long_prefill, args.long_decode, True)
    ok &= run("system", short, n_dec, True)
    for m in range(1, Bk + 1):
        ok &= run(f"masks_{m}", n_pre, n_dec, True, masks=m)
    ok &= run(f"masks_{Bk // 2}", args.long_prefill, args.long_decode, True,
              masks=Bk // 2)
    # each control is scored where it moves the logits: all positions;
    # at the short context the decoded ones (a block's last position
    # sees the same keys under both masks) and the last block
    ok &= run("causal_prefill", n_pre, n_dec, False, control="causal_prefill")
    ok &= run("causal_block", short, n_dec, False, control="causal_block",
              positions=decoded)
    ok &= run("stale_commit", short, n_dec, False, control="stale_commit",
              positions=last)
    ok &= run("shifted", n_pre, n_dec, False, control="shifted")
    ok &= run("float8", n_pre, n_dec, False, float8=True)  # last: it rounds
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
    ap.add_argument("--first-seed", type=int, default=2147489001)
    ap.add_argument("--sequences", type=int, default=8)
    ap.add_argument("--prefill", type=int, default=correctness.N_PREFILL)
    ap.add_argument("--short-prefill", type=int, default=8)
    ap.add_argument("--long-prefill", type=int, default=1984)
    ap.add_argument("--long-decode", type=int, default=64)
    ap.add_argument("--tail", type=int, default=16)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("bd_numbers: this only runs on the chip (or --cpu)",
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
    (out / f"{cfg['name']}.bd_numbers.json").write_text(
        json.dumps(rows, indent=1))
    print("bd_numbers:", "every leg as it must" if all_ok else "FAILED")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
