"""Bench-record self-grading (engine/roofline.py, VERDICT r3 weak #5):
analytic bytes-per-step / roofline fractions computed from the model
config, present in every bench record."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from sutro_tpu.engine import roofline

REPO = Path(__file__).resolve().parent.parent


def test_hw_specs_lookup():
    assert roofline.hw_specs("TPU v5 lite") == (819.0, 197.0)
    assert roofline.hw_specs("TPU v4") == (1228.0, 275.0)
    assert roofline.hw_specs("cpu") is None
    assert roofline.hw_specs("") is None


def test_decode_bytes_per_step_arithmetic():
    # params + B * (ctx+1) * L*2*KVH*Dh*kv_bytes
    b = roofline.decode_bytes_per_step(
        param_bytes=1_000_000,
        batch=4,
        avg_ctx=99,
        num_layers=2,
        kv_heads=2,
        head_dim=8,
        kv_dtype_bytes=2,
    )
    assert b == 1_000_000 + 4 * (2 * 2 * 2 * 8 * 2) * 100


def test_grade_decode_fraction():
    # choose numbers so the fraction is exactly 50%: bytes/step = 819e9
    # bytes/s at 1 step/s would be 100%; run at 0.5 step/s
    g = roofline.grade_decode(
        32.0,  # tok/s at batch 64 -> 0.5 steps/s
        batch=64,
        bytes_per_step=819.0e9,
        device_kind="TPU v5 lite",
    )
    assert g["pct_hbm_roofline"] == pytest.approx(50.0)
    assert g["hbm_gb_s"] == 819.0
    # unknown hardware: grade omitted, never fabricated
    g2 = roofline.grade_decode(
        32.0, batch=64, bytes_per_step=1e9, device_kind="cpu"
    )
    assert g2["pct_hbm_roofline"] is None


def test_grade_prefill_mfu():
    # 2 * 1e9 params * tok_s / (197e12) => choose tok_s for mfu=10%
    tok_s = 0.10 * 197e12 / (2 * 1e9)
    g = roofline.grade_prefill(
        tok_s, n_params=1_000_000_000, device_kind="TPU v5 lite"
    )
    assert g["mfu_prefill"] == pytest.approx(10.0)
    assert (
        roofline.grade_prefill(1.0, n_params=1, device_kind="x")[
            "mfu_prefill"
        ]
        is None
    )


def test_param_bytes_counts_quantized_width():
    import numpy as np

    params = {
        "w": np.zeros((4, 4), np.int8),
        "s": np.zeros((4,), np.float32),
    }
    assert roofline.param_bytes_of(params) == 16 + 16
    assert roofline.param_count_of(params) == 20


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """chip_smoke.py never passes for a stand-in backend: without a TPU
    (and without --cpu-rehearsal) it exits non-zero, names the platform
    it found, and prints no ok line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=tmp_path,
    )
    assert r.returncode != 0, r.stdout + r.stderr
    assert "'cpu'" in r.stderr
    assert '{"ok": true' not in r.stdout
