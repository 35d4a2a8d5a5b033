"""The CPU rehearsal of the cell of a model whose blocks are one
sublayer and whose experts are one rank's share
(``tiny-nemotron-h.generate-long-output-jobs``, entries in
``rehearsal/cells-nemotron-h.json``, run by ``tools/rehearse_added.py``
over a copy of the benchmark): the control flow, the routed numbers
check through the state slots and the paged cache against a reference
given the same share, and the readers this family brings."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-nemotron-h.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-nemotron-h.generate-long-output-jobs"


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
        (REPO / "perfbench/traffic/generate-long-output-jobs.json").read_text())
    short = json.loads(
        (REPO / "perfbench/traffic/generate-short-jobs.json").read_text())
    assert (t["generator"], t["clients"]) == ("batch_jobs", 32)
    assert t["rows_per_job"] == {"of_decode_batch": 0.0625}
    assert t["prompt_chars"] == short["prompt_chars"]
    # ISSUE 40's cycle (384-640, mean 512) shortened as it said to when
    # the first set of six spread over 5 %
    assert t["max_new_tokens_cycle"] == [256, 384, 320, 288, 352, 320]
    assert sum(t["max_new_tokens_cycle"]) / 6 == 320
    assert t["sampling"] == {"temperature": 0.7}
    assert t["output_schema"] is None and t["system_prompt"] is None
    assert t["lead_in_s"] == 20.0
    cfg = json.loads((REPO / "perfbench/configs/"
                      "nemotron-3-nano-30b-a3b-l14-ep2-v5e1.json").read_text())
    eng = cfg["engine"]
    # 16 rows a job, 512 outstanding: twice the batch
    rows = int(t["rows_per_job"]["of_decode_batch"] * eng["decode_batch_size"])
    assert rows == 16 and t["clients"] * rows == 2 * eng["decode_batch_size"]
    # the longest row is 1,075 tokens of a context of 2,048; no prompt
    # is over the prefill chunk
    longest = t["prompt_chars"]["long_max"] + 19 + max(t["max_new_tokens_cycle"])
    assert longest == 1075 <= eng["max_model_len"] == eng["prefill_chunk"]
    assert eng["max_pages_per_seq"] * eng["kv_page_size"] >= eng["max_model_len"]
    # a warm group a prefill bucket and a job size
    groups = t["warm"]["groups"]
    assert {g["rows"] for g in groups} >= set(range(1, 17))
    assert [g["chars"] for g in groups if g["rows"] == 1] == [60, 180, 400, 600]


@pytest.mark.parametrize("trace,expect", [
    (0, {"out_tokens_per_s_per_chip", "setup_s"}),
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "moe_expert_rows_max_over_mean", "state_fallback_prefill_share",
         "state_slot_occupancy", "decode_row_steps_kept_share",
         "moe_rows_held_share", "decode_batch_occupancy"}),
])
def test_rehearsal_of_the_held_share_cell(trace, expect):
    proc = rehearse(
        "--workload", CELL, "--seed", str(2**31 + 13),
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
    # float32 against float32 through the slots and the paged cache, by
    # the routed rule: on a CPU no routing flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == 1_401_792
    if trace:
        share = result["metrics"]["moe_rows_held_share"]["value"]
        # 4 of 8 experts held, random weights: about half
        assert 0.3 < share < 0.7
        assert result["metrics"]["state_slot_occupancy"]["value"] > 0.0
        assert result["metrics"]["moe_expert_rows_max_over_mean"]["value"] >= 1.0
        # the device metrics are skipped on a CPU, not failed
        for name in ("ssm_moe_decode_hbm_roofline",
                     "grouped_matmul_decode_hbm_roofline"):
            assert name not in result["metrics"]
