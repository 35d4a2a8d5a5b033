"""The CPU rehearsal of the cell of a model of delta-rule (KDA) layers
beside gated NoPE GQA with a held share of its experts
(``tiny-solar-kda.generate-long-output-jobs``, entries in
``rehearsal/cells-solar.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the routed numbers check
through the delta-rule state slots and the paged cache against a
reference given the same share, and the readers this family brings."""

import json
import os
import subprocess
import sys

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-solar.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-solar-kda.generate-long-output-jobs"


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


def test_the_timed_cell_takes_the_traffic_file_that_is_there():
    t = json.loads(
        (REPO / "perfbench/traffic/generate-long-output-jobs.json").read_text())
    cfg = json.loads((REPO / "perfbench/configs/"
                      "solar-open2-250b-l8-ep16-v5e1.json").read_text())
    eng = cfg["engine"]
    # a sixteenth of the batch a job: 12 rows, 384 outstanding = twice the batch
    rows = int(t["rows_per_job"]["of_decode_batch"] * eng["decode_batch_size"])
    assert rows == 12 and t["clients"] * rows == 2 * eng["decode_batch_size"]
    longest = t["prompt_chars"]["long_max"] + 19 + max(t["max_new_tokens_cycle"])
    assert longest == 1075 <= eng["max_model_len"] == eng["prefill_chunk"]
    assert eng["max_pages_per_seq"] * eng["kv_page_size"] >= eng["max_model_len"]
    assert {g["rows"] for g in t["warm"]["groups"]} >= set(range(1, rows + 1))
    assert t["output_schema"] is None and t["system_prompt"] is None


def test_rehearsal_of_the_delta_rule_cell_traced():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "perfbench/tools/rehearse_added.py", "--cells",
         str(ADDED), "--workload", CELL, "--seed", str(2**31 + 50),
         "--seconds", "8", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert {"engine_host_us_per_row", "tokens_per_dispatch",
            "moe_expert_rows_max_over_mean", "state_fallback_prefill_share",
            "state_slot_occupancy", "decode_row_steps_kept_share",
            "decode_batch_occupancy",
            "kda_state_bytes_moved_over_needed"} <= set(result["metrics"])
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    # float32 against float32 through the slots and the paged cache, by
    # the routed rule: on a CPU no routing flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == 978_224
    # the XLA forms gather a row's slot and scatter it back: about three
    # times the need (the kernels' 1.11 is a chip reading)
    moved = result["metrics"]["kda_state_bytes_moved_over_needed"]["value"]
    assert 2.0 < moved < 4.0
    assert result["metrics"]["state_slot_occupancy"]["value"] > 0.0
    # the device metrics are skipped on a CPU, not failed
    for name in ("kda_gqa_moe_decode_hbm_roofline", "kda_prefill_mxu_roofline",
                 "kda_state_read_hbm_roofline", "kda_state_commit_hbm_roofline"):
        assert name not in result["metrics"]
