"""The CPU rehearsal of the cell of a model that keeps K/V a pool a kind
(``tiny-mellum2.generate-long-prompt-jobs``, entries in
``rehearsal/cells-mellum2.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the numbers check through
``logits_through_cache`` with ONE table and the identity map, window
pages bound and released under traffic, and the readers this family
brings."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-mellum2.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-mellum2.generate-long-prompt-jobs"


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


def test_the_timed_traffic_is_the_issues_table():
    t = json.loads(
        (REPO / "perfbench/traffic/generate-long-prompt-jobs.json").read_text())
    assert (t["generator"], t["clients"]) == ("batch_jobs", 16)
    assert t["rows_per_job"] == {"of_decode_batch": 0.125}
    assert t["prompt_chars"] == {
        "pool": 32, "pool_seed": 20260929, "median": 1450, "sigma": 0.25,
        "min": 1100, "max": 2000, "long_every": 8, "long_min": 2600,
        "long_max": 3400}
    assert t["max_new_tokens_cycle"] == [96, 160, 128, 112, 144, 128]
    assert t["sampling"] == {"temperature": 0.7}
    assert t["output_schema"] is None and t["system_prompt"] is None
    assert t["lead_in_s"] == 20.0
    # every prompt is past the window, none over the prefill chunk
    cfg = json.loads((REPO / "perfbench/configs/"
                      "mellum2-12b-a2.5b-l8-v5e1.json").read_text())
    assert t["prompt_chars"]["min"] > cfg["sliding_window"]
    assert (t["prompt_chars"]["long_max"] + 19 + max(t["max_new_tokens_cycle"])
            <= cfg["engine"]["max_model_len"] == cfg["engine"]["prefill_chunk"])
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]


@pytest.mark.parametrize("trace,expect", [
    (0, {"out_tokens_per_s_per_chip", "setup_s"}),
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "moe_expert_rows_max_over_mean", "state_fallback_prefill_share",
         "decode_kv_pages_fetched_over_needed", "window_kv_held_share",
         "decode_batch_occupancy"}),
])
def test_rehearsal_of_the_window_pool_cell(trace, expect):
    proc = rehearse(
        "--workload", CELL, "--seed", str(2**31 + 11),
        "--seconds", "8", "--trace", str(trace),
    )
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert expect <= set(result["metrics"])
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    # float32 against float32 through the map and the paged cache, by the
    # routed rule: on a CPU no routing flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == facts["params"]["from_shapes"]
    if trace:
        held = result["metrics"]["window_kv_held_share"]["value"]
        # a window of 8 under prompts of 55-160 tokens: pages went back
        assert 0.0 < held < 0.5
        assert result["metrics"]["decode_kv_pages_fetched_over_needed"][
            "value"] >= 1.0
        # the device metric is skipped on a CPU, not failed
        assert "swa_moe_decode_hbm_roofline" not in result["metrics"]
