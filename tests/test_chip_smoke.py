"""chip_smoke.py off the chip: it must refuse, and its rehearsal must
never pass for a device run."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(*flags, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *flags],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )


def _result_lines(stdout: str) -> list:
    """Lines that parse as the contract's ``{"ok": ...}`` object."""
    found = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            found.append(obj)
    return found


def test_chip_smoke_refuses_without_a_tpu():
    """No TPU => non-zero exit, the message names the platform found,
    and no result line: the script never picks the CPU by itself."""
    r = _run(timeout=300)
    assert r.returncode != 0, r.stdout + r.stderr
    assert "'cpu'" in r.stderr and "not a TPU" in r.stderr
    assert _result_lines(r.stdout) == []


@pytest.mark.slow  # ~40 s: a tiny engine + daemon through every phase
def test_cpu_rehearsal_runs_and_says_so_in_every_line():
    r = _run("--cpu-rehearsal", timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert lines and all(ln.startswith("[CPU REHEARSAL") for ln in lines)
    assert _result_lines(r.stdout) == []
