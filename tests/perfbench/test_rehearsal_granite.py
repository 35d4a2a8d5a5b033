"""The CPU rehearsal of the cell with mamba layers
(``tiny-granite.generate-short-jobs``, entries in
``rehearsal/cells-granite.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the numbers check through
``logits_through_cache`` with the state a slot a sequence beside the
paged cache, and the readers this model brings."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of, window_with

ADDED = REPO / "perfbench/rehearsal/cells-granite.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-granite.generate-short-jobs"


def rehearse(*flags, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "perfbench/tools/rehearse_added.py", "--cells",
         str(ADDED), *flags],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def test_added_entries_fit_beside_the_rehearsal_file():
    added = json.loads(ADDED.read_text())
    names = {c["name"] for c in CELLS["configs"]} | {
        w["name"] for w in CELLS["workloads"]}
    for cfg in added["configs"]:
        assert cfg["name"] not in names
        assert (REPO / cfg["file"]).is_file()
    for cell in added["workloads"]:
        assert cell["name"] not in names
        assert cell["config"] in {c["name"] for c in added["configs"]}
        assert cell["stands_for"] in {w["name"] for w in BENCH["workloads"]}
        assert (REPO / "perfbench/rehearsal/traffic"
                / f"{cell['traffic']}.json").is_file()
    assert CELL not in {w["name"] for w in CELLS["workloads"]}


@pytest.mark.parametrize("trace,expect", [
    (0, {"out_tokens_per_s_per_chip", "setup_s"}),
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "state_slot_occupancy", "state_fallback_prefill_share",
         "decode_batch_occupancy"}),
])
def test_rehearsal_of_the_state_slots_cell(trace, expect):
    proc = window_with(lambda seconds: rehearse(
        "--workload", CELL, "--seed", str(2**31 + 9),
        "--seconds", str(seconds), "--trace", str(trace),
    ), expect)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert expect <= set(result["metrics"])
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    # float32 against float32 through the state slots and the paged
    # cache, position by position (the dense rule)
    assert "rule" not in numbers and numbers["dtype"] == "float32"
    assert max(numbers["rel_err_prefill"], numbers["rel_err_decode_max"]) < 2e-4
    if trace:
        slots = result["metrics"]["state_slot_occupancy"]["value"]
        rows = result["metrics"]["decode_batch_occupancy"]["value"]
        assert 0.0 < slots <= 100.0 and 0.0 < rows <= 100.0
        # the device metric is skipped on a CPU, not failed
        assert "ssm_hybrid_decode_hbm_roofline" not in result["metrics"]
