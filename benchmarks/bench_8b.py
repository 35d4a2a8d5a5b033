"""Realistically-sized models on one chip (verdict r2 item 5).

Everything chip-side so far ran qwen3-0.6b; the 32B-TP north star's
per-chip behavior is MLP-dominated and HBM-bound, which a 0.6B model
does not predict. This driver benches larger dense models through the
same bench.py decode/prefill loop and reports the HBM-roofline fraction
— the actual predictor for big-model per-chip efficiency.

Configs (chosen for a 16 GB-HBM v5e chip):
  qwen3-4b bf16       (~8 GB weights — fits)
  qwen3-4b int8       (~4 GB — headroom for bigger batches)
  llama-3.1-8b int8   (~8 GB — bf16 would not fit one chip)

Each config runs ``bench.py`` in a subprocess of its own (one process
per chip: this parent never touches JAX, so each child finds the chip
free) and the analytic weight-byte count gives
roofline_frac = bytes_touched_per_second / HBM_BW. Decode at these
sizes is weight-bandwidth-bound, so bytes/step ~ param_bytes.

Writes BENCH_8B.json; skips with a clear record when run off-TPU.
Env: SUTRO_8B_CONFIGS="model:quant,model:quant" overrides the set.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

V5E_HBM_GBS = 819.0  # v5e HBM bandwidth, public chip spec (GB/s)

DEFAULT_CONFIGS = [
    ("qwen3-4b", None, 64),
    ("qwen3-4b", "int8", 64),
    ("llama-3.1-8b", "int8", 32),
]


def param_bytes(model_key: str, quant: str | None) -> int:
    """Shape-only param count — computed in an EXPENDABLE subprocess
    pinned to CPU. This driver process never touches the JAX backend:
    a chip belongs to one process at a time, and a parent that held
    it would starve every bench child."""
    import sys

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu');\n"
        "from sutro_tpu.models import transformer\n"
        "from sutro_tpu.models.configs import MODEL_CONFIGS\n"
        f"mcfg = MODEL_CONFIGS[{model_key!r}]\n"
        "shapes = jax.eval_shape(lambda: transformer.init_params("
        "mcfg, jax.random.PRNGKey(0), 'bfloat16'))\n"
        "print(sum(int(x.size) for x in "
        "jax.tree_util.tree_leaves(shapes)))"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=300,
    )
    n_params = int(r.stdout.strip().splitlines()[-1])
    per = 1 if quant == "int8" else 2
    return n_params * per


def main() -> int:
    sys.path.insert(0, str(REPO))
    from sutro_tpu.engine.softdeadline import arm_from_env

    arm_from_env()  # clean self-exit before any outer kill (see module)
    cfgs = DEFAULT_CONFIGS
    override = os.environ.get("SUTRO_8B_CONFIGS")
    if override:
        cfgs = []
        for part in override.split(","):
            name, _, q = part.strip().partition(":")
            cfgs.append((name, q or None, 32))

    results = []
    for model, quant, batch in cfgs:
        env = dict(os.environ)
        env["SUTRO_BENCH_MODEL"] = model
        env["SUTRO_BENCH_BATCH"] = str(batch)
        if quant:
            env["SUTRO_BENCH_QUANT"] = quant
        else:
            env.pop("SUTRO_BENCH_QUANT", None)
        # the child must self-exit (clean PJRT teardown, chip
        # released) before subprocess.run's timeout SIGKILLs it — an
        # inherited parent-budget deadline would let the child outlive
        # this inner timeout
        env["SUTRO_SOFT_DEADLINE_S"] = "3420"
        print(
            f"== {model} quant={quant or 'bf16'} bs={batch}",
            file=sys.stderr, flush=True,
        )
        # Popen (not subprocess.run): run()'s exception path SIGKILLs
        # the child — if this parent's own soft deadline interrupts a
        # blocking wait, that would hard-kill a child actively holding
        # the chip. TERM instead: the child's softdeadline handler
        # exits cleanly.
        proc = subprocess.Popen(
            [sys.executable, str(REPO / "bench.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        try:
            out, err = proc.communicate(timeout=3600)
            line = (out.strip().splitlines() or [""])[-1]
            try:
                bench = json.loads(line)
            except json.JSONDecodeError:
                bench = {"metric": "parse-error", "value": 0,
                         "raw": out[-500:] + err[-500:]}
        except subprocess.TimeoutExpired:
            # child's own 3420s soft deadline should have fired; TERM
            # takes its clean path, record and keep measured configs
            proc.terminate()
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()
            bench = {"metric": "bench-timeout (3600s)", "value": 0,
                     "unit": "error"}
        except BaseException:
            # parent interrupted (soft deadline / TERM): give the
            # child its clean exit, persist the configs already
            # measured (hours of chip time), then propagate
            proc.terminate()
            try:
                proc.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
            if results:  # never clobber a prior run's artifact with
                _write(results)  # an empty record set
            raise
        rec = {
            "model": model,
            "quant": quant or "bf16",
            "batch": batch,
            "bench": bench,
        }
        if bench.get("unit") == "tok/s/chip" and bench.get("value"):
            pb = param_bytes(model, quant)
            tok_s = float(bench["value"])
            steps_per_s = tok_s / batch
            gbs = pb * steps_per_s / 1e9
            rec.update(
                param_bytes=pb,
                weight_stream_gb_s=round(gbs, 1),
                hbm_roofline_frac=round(gbs / V5E_HBM_GBS, 3),
            )
        results.append(rec)
        print(json.dumps(rec), flush=True)
        _write(results)  # persist after EVERY config: a later
        #                  interrupt must not discard measured records

    return 0


def _write(results: list) -> None:
    # backend comes from the subprocess records (this process never
    # touches the JAX backend — see param_bytes)
    backends = {
        m.group(1)
        for r in results
        for m in [re.search(r", (\w+)\)$", r["bench"].get("metric", ""))]
        if m
    }
    out = {
        "backend": sorted(backends)[0] if len(backends) == 1 else sorted(
            backends
        ),
        "hbm_bw_gb_s": V5E_HBM_GBS,
        "records": results,
    }
    (REPO / "BENCH_8B.json").write_text(json.dumps(out, indent=2) + "\n")


if __name__ == "__main__":
    main()
