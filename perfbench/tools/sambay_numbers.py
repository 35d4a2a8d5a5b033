#!/usr/bin/env python3
"""The numbers check of a decoder-hybrid-decoder at the lengths its cell
is TIMED at, with the controls of its own mechanisms.
``correctness.numbers``'s 192 + 8 positions end before a window of 512
begins, and ``tools/long_numbers.py`` takes a routed file's ``numbers``
key, which a dense file does not state: so this tool runs the
benchmark's own ``sut.logits_through_cache(ids, n_prefill, n_decode)`` at
each ``--prefill`` length (default 1,100 and 1,900: past the window, and
near the longest timed row) with ``--decode`` steps (16) against
``reference/sambay_diff.py`` under the DENSE rule (every scored position
within the dtype's tolerance), which has to PASS, and against five
controls, each of which has to FAIL (a system that took a control's
form would pass it and fail the reference):

- ``no_window``: ``sliding_window`` taken from the reference (the window
  layers attend over everything);
- the reference's ``variant``s: ``cross_reads_window`` (the cross layers
  see layer 17's last 512 positions alone), ``no_lambda`` (the second
  softmax left out), ``memory_after_gate`` (the memory units take
  ``y * silu(z)``);
- ``float8``: the system on weights rounded through float8_e4m3 against
  the reference on the weights as stated (the shortest length, last: it
  rounds the runner's weights in place).

    python3 perfbench/tools/sambay_numbers.py --config <configs/*.json> \\
        [--seed N] [--prefill 1100 1900] [--sequences 2]

One ``ModelRunner`` with the file's engine settings and the weights the
engine would build for ``--seed``. Needs the chip unless ``--cpu`` (the
rehearsal's file, short lengths). The JSON goes to
``chiprun_out/perfbench/<name>.sambay_numbers.json``; the exit code is 0
when every length passes and every control fails.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import correctness  # noqa: E402
from perfbench.reference import sambay_diff  # noqa: E402
from perfbench.tools import numbers_readings as nr  # noqa: E402


def errors(cfg, params, ids, got, positions, variant=None):
    """``max |system - reference| / max |reference|`` a scored position,
    ``[sequences, positions]``."""
    import numpy as np

    want = np.stack([
        np.asarray(sambay_diff.logits_at(
            cfg, params, seq, positions, variant=variant), np.float32)
        for seq in ids
    ])
    return correctness.position_errors(got[: len(ids)], want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=2147489257)
    ap.add_argument("--prefill", type=int, nargs="+", default=[1100, 1900])
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--control-sequences", type=int, default=1)
    ap.add_argument("--variants", nargs="+", default=list(sambay_diff.VARIANTS))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("sambay_numbers: this only runs on the chip (or --cpu)",
              file=sys.stderr)
        return 3
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = json.loads((REPO / args.config).read_text())
    settings = dict(cfg["engine"], seed=int(args.seed) % (2**31 - 1))
    runner = ModelRunner(
        MODEL_CONFIGS[cfg["engine_key"]], EngineConfig(**settings), num_pages=2,
    )
    sut = nr.stand_in(runner, cfg)
    dtype = settings["param_dtype"]
    tol = float(json.loads(
        (HERE / "reference/tolerance.json").read_text())[dtype])
    no_window = dict(cfg, sliding_window=1 << 30)
    rows, ok = [], True

    def inputs(n_pre):
        rng = np.random.default_rng([int(args.seed), n_pre, 0x5A3B])
        ids = rng.integers(
            0, 256, (args.sequences, n_pre + args.decode)
        ).astype(np.int32)
        return ids, list(range(n_pre - 1, n_pre + args.decode))

    for n_pre in args.prefill:
        ids, positions = inputs(n_pre)
        got = sut.logits_through_cache(ids, n_pre, args.decode)
        errs = errors(cfg, runner.params, ids, got, positions)
        passed = bool(errs.max() <= tol)
        row = {"seed": args.seed, "n_prefill": n_pre, "n_decode": args.decode,
               "sequences": args.sequences, "passed": passed,
               "rel_err_max": float(errs.max()),
               "rel_err_median": float(np.median(errs)), "controls": {}}
        ok = ok and passed
        line = (f"prefill {n_pre} + {args.decode}: "
                f"{'PASS' if passed else 'FAIL'} max {errs.max():.4f} median "
                f"{np.median(errs):.4f} (limit {tol})")
        few = ids[: args.control_sequences]
        legs = [("no_window", no_window, None)] + [
            (v, cfg, v) for v in args.variants]
        for name, keys, variant in legs:
            c = errors(keys, runner.params, few, got, positions, variant)
            failed = bool(c.max() > tol)
            row["controls"][name] = {
                "failed": failed, "rel_err_max": float(c.max())}
            ok = ok and failed
            line += f" | {name}: {'fails' if failed else 'PASSES'} {c.max():.3f}"
        rows.append(row)
        print(line, flush=True)
    # float8, last: the reference on the weights as stated, the system
    # on the rounded ones
    n_pre = min(args.prefill)
    ids, positions = inputs(n_pre)
    few = ids[: args.control_sequences]
    want = np.stack([
        np.asarray(sambay_diff.logits_at(cfg, runner.params, seq, positions),
                   np.float32)
        for seq in few
    ])
    runner.params = nr.through_float8(runner.params)   # donates the stated
    got = sut.logits_through_cache(few, n_pre, args.decode)
    c = correctness.position_errors(got, want)
    failed = bool(c.max() > tol)
    ok = ok and failed
    rows.append({"control": "float8", "n_prefill": n_pre, "failed": failed,
                 "rel_err_max": float(c.max())})
    print(f"float8 at {n_pre} + {args.decode}: "
          f"{'fails' if failed else 'PASSES'} {c.max():.3f}", flush=True)
    from sutro_tpu.engine.runner import device_report

    report = {
        k: v for k, v in device_report(runner.ecfg).items()
        if k in ("kernel_paths", "kernel_heads", "paged_chunk", "mamba1",
                 "paged_decode_xla", "flash_prefill")
    }
    print("device_report", json.dumps(report), flush=True)
    out = REPO / "chiprun_out" / "perfbench"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cfg['name']}.sambay_numbers.json").write_text(
        json.dumps({"lengths": rows, "device_report": report}, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
