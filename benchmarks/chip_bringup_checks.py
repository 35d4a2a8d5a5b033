"""Two bring-up checks the builder runs on the chip beside chip_smoke.py
(ISSUE 21; results in PERF.md "Bring-up"). Each is ONE process and, like
chip_smoke.py, refuses to run without a TPU.

  default-config  `so.infer(rows, model="qwen-3-4b")` with NO engine_config
                  on one chip: the default sizing (64 slots x 8192 context
                  = 77 GB of KV at this model) must come out as a pool
                  bounded by the device's memory, not RESOURCE_EXHAUSTED.
  tp4             qwen3-8b (16 GB in bf16) over tp=4 through LocalEngine on
                  a four-chip host: >=32 rows succeed, every device holds
                  the same share (max/min bytes_in_use <= 1.5), and the
                  report names the attention path that ran under the mesh.

Usage: python benchmarks/chip_bringup_checks.py {default-config|tp4}
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(which: str) -> int:
    os.environ["SUTRO_HOME"] = tempfile.mkdtemp(prefix="sutro-bringup-")
    import jax

    from chip_smoke import finished_job  # same row checks as the smoke
    from sutro_tpu.engine.runner import device_report
    from sutro_tpu.ops import lowering
    from sutro_tpu.sdk import Sutro

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_bringup_checks: JAX found platform {devs[0].platform!r}, "
            "not a TPU"
        )
    # mixed lengths: short rows (jnp prefill below the flash tile), rows
    # past 128 tokens (flash prefill) and a few past prefill_chunk
    # (chunked prefill over a paged past) — the byte tokenizer makes a
    # token of every character
    rows = [
        f"Review {i}: the battery lasts {i + 1} days (order #{1000 + i})"
        + " and it charges fast" * (0, 6, 0, 9, 0, 6, 0, 30)[i % 8]
        for i in range(32)
    ]
    if which == "default-config":
        so, model, key = Sutro(api_key="bringup"), "qwen-3-4b", "qwen3-4b"
    elif which == "tp4":
        if len(devs) < 4:
            raise SystemExit(f"tp4 needs four chips, found {len(devs)}")
        so = Sutro(
            api_key="bringup",
            engine_config=dict(
                tp=4, decode_batch_size=64, kv_page_size=64,
                max_pages_per_seq=16, max_model_len=1024,
            ),
        )
        model, key = "qwen-3-8b", "qwen3-8b"
    else:
        raise SystemExit(__doc__)
    print(json.dumps({"device_report": device_report(so.engine.ecfg)}))
    t0 = time.monotonic()
    job = so.infer(
        rows, model=model, stay_attached=False,
        sampling_params={"max_new_tokens": 64},
    )
    facts = finished_job(so, so.engine, job, len(rows))
    runner = so.engine._runner_cache[key][0]
    in_use = [int(d.memory_stats()["bytes_in_use"]) for d in devs]
    spread = max(in_use) / max(min(in_use), 1)
    out = {
        "check": which,
        "status": facts["status"],
        "rows": facts["rows"],
        "gen_tokens_total": facts["gen_tokens_total"],
        "wall_s": round(time.monotonic() - t0, 1),
        "runner": runner.device_info(),
        "alloc_pages": runner.alloc_pages,
        "bytes_in_use_per_device": in_use,
        "bytes_in_use_max_over_min": round(spread, 3),
        "kernel_paths": lowering.snapshot(),
    }
    so.engine.close(timeout=30)
    if which == "tp4" and spread > 1.5:
        raise SystemExit(f"tp4: devices hold unequal shares: {in_use}")
    out_dir = Path(__file__).resolve().parent.parent / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"bringup_{which}.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else ""))
