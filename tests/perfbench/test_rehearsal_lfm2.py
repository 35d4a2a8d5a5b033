"""The CPU rehearsal of the cell with layers of several kinds
(``tiny-lfm2.generate-jobs``, entries in ``rehearsal/cells-lfm2.json``,
run by ``tools/rehearse_added.py`` over a copy of the benchmark): the
control flow, the numbers check through ``logits_through_cache`` with the
conv state beside the paged cache, and the readers this model brings."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of, window_with

ADDED = REPO / "perfbench/rehearsal/cells-lfm2.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())


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


@pytest.mark.parametrize("trace,expect", [
    (0, {"out_tokens_per_s_per_chip", "setup_s"}),
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "moe_expert_rows_max_over_mean", "state_fallback_prefill_share"}),
])
def test_rehearsal_of_the_mixed_layers_cell(trace, expect):
    proc = window_with(lambda seconds: rehearse(
        "--workload", "tiny-lfm2.generate-jobs", "--seed", str(2**31 + 9),
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
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    # float32 against float32 through the conv state and the paged cache
    assert numbers["rel_err_max"] < 2e-4 and numbers["share_over_tolerance"] == 0.0
    if trace:
        # no prompt token was prefilled again for want of state
        assert result["metrics"]["state_fallback_prefill_share"]["value"] == 0.0
        assert result["metrics"]["moe_expert_rows_max_over_mean"]["value"] >= 1.0


def test_the_plain_rehearsal_does_not_know_the_added_cell():
    """``cells.json`` is as the benchmark had it: the cell runs only
    through the tool."""
    assert "tiny-lfm2.generate-jobs" not in {
        w["name"] for w in CELLS["workloads"]}
