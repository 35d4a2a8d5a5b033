"""The CPU rehearsal of every cell's control flow, end to end, as a
process of its own (tiny-dense and tiny-moe files kept apart from
BENCHMARK.json): the last line parses and has the contract's keys,
every line is tagged, no device metric's name is printed, a refused chat
lands in ``failed`` and never in ``correct``. And off the chip the real
cells refuse."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
TAG = "[CPU REHEARSAL - not a device run] "
DEVICE_WORDS = [
    m["name"] for m in BENCH["per_layer"] if m["source"] == "device_trace"
] + ["busy_s", "window_s", "breakdown"]


def run(*flags, cwd=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], *flags], env=env,
        capture_output=True, text=True, timeout=timeout, cwd=cwd,
    )


WINDOWS = (8, 24)


def window_with(run_once, expect, windows=WINDOWS):
    """``run_once(seconds)``'s process whose result line holds every
    metric of ``expect``. A reader reads a finished row or the second of
    two progress updates, and a loaded machine can leave an 8 s CPU
    window without one: the rate then has no reading (exit 4) or a
    per-layer reader leaves its metric out. So the window is not left to
    the clock alone: where the first lacks a reading the rehearsal runs
    once more, three times as long, and that window is the limit."""
    for seconds in windows:
        proc = run_once(seconds)
        lines = proc.stdout.splitlines()
        if proc.returncode == 0 and lines and lines[-1].startswith(TAG):
            result = json.loads(lines[-1][len(TAG):])
            if result["attempted"] > 0 and expect <= set(result["metrics"]):
                break
    return proc


def result_of(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert lines and all(ln.startswith(TAG) for ln in lines)
    for word in DEVICE_WORDS:
        assert word not in proc.stdout, word
    result = json.loads(lines[-1][len(TAG):])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]   # compared comes last
    for number, limit in result["compared"].values():
        assert isinstance(number, (int, float)) and isinstance(limit, (int, float))
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    return result


@pytest.mark.parametrize("cell,trace,expect", [
    ("tiny.generate-jobs", 0, {"out_tokens_per_s_per_chip", "setup_s"}),
    ("tiny.generate-jobs", 1, {"tokens_per_dispatch", "engine_host_us_per_row"}),
    ("tiny.classify-jobs", 0, {"job_turnaround_s", "setup_s"}),
    ("tiny.classify-jobs", 1, {"decode_burst_tokens_per_s", "constraint_build_share"}),
    ("tiny.chat-over-jobs", 0, {"out_tokens_per_s_per_chip", "ttft_p95_ms",
                                "tpot_p95_ms", "setup_s"}),
])
def test_rehearsal_of_a_cell(cell, trace, expect):
    result = result_of(run(
        "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "10",
        "--trace", str(trace), "--cpu-rehearsal",
    ))
    assert result["correct"] is True
    # a chat that gets no token by the drain's end on a busy CPU is a
    # failed operation by design, never a wrong output; jobs never fail
    late_chats_allowed = 2 if "chat" in cell else 0
    assert result["failed"] <= late_chats_allowed
    assert result["attempted"] > 0
    assert expect <= set(result["metrics"])
    assert all(result["metrics"][name]["value"] > 0 for name in expect)


def test_rehearsal_of_the_routed_cell():
    """tiny-moe (4 experts, top-2): ``correct`` is decided by the routed
    rule, and the configuration file's sizes count exactly the
    parameters the program serves."""
    proc = run(
        "--workload", "tiny-moe.generate-jobs", "--seed", str(2**31 + 9),
        "--seconds", "8", "--trace", "1", "--cpu-rehearsal",
    )
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert {"tokens_per_dispatch", "engine_host_us_per_row"} <= set(result["metrics"])
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    assert numbers["rule"] == "routed" and numbers["positions"] == 4 * 9
    assert numbers["dtype"] == "float32" and numbers["tolerance"] == 0.002
    # float32 against float32: no routing flips, every position agrees
    assert numbers["rel_err_max"] < 2e-4 and numbers["share_over_tolerance"] == 0.0
    # 2 blocks of attention, a router and 4 experts of 3 x 128 x 128, untied
    params = facts["params"]
    assert params["from_shapes"] == params["served"] == 624_384
    assert params["per_token"] == 624_384 - 2 * 2 * 3 * 128 * 128


def test_a_refused_chat_lands_in_failed_not_in_correct():
    result = result_of(run(
        "--workload", "tiny.refused-over-jobs", "--seed", "3", "--seconds", "10",
        "--trace", "0", "--cpu-rehearsal",
    ))
    assert result["correct"] is True
    assert 0 < result["failed"] < result["attempted"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_real_cell_refuses_to_run_without_its_chips(cell):
    proc = run("--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0",
               timeout=300)
    assert proc.returncode != 0
    assert "only runs on the chip" in proc.stderr
    assert proc.stdout.strip() == ""


def test_alone_with_only_the_benchmark_files_it_refuses(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
               "--seconds", "1", "--trace", "0", cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_an_unknown_cell_is_an_error():
    proc = run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
               "--trace", "0", timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
