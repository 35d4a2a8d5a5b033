"""The CPU rehearsal of the cell of a model whose residual stream is four
lanes mixed a token in every sublayer round YaRN-scaled latent attention
(``tiny-xing-mhc.generate-long-prompt-jobs``, entries in
``rehearsal/cells-xing.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the routed numbers check
through the latent cache against the plain reference, and the readers
this family brings."""

import json
import os
import subprocess
import sys

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-xing.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-xing-mhc.generate-long-prompt-jobs"


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
        (REPO / "perfbench/traffic/generate-long-prompt-jobs.json").read_text())
    cfg = json.loads((REPO / "perfbench/configs/"
                      "xing4.0-29b-a4b-l7-v5e1.json").read_text())
    eng = cfg["engine"]
    # an eighth of the batch a job: 16 rows, 256 outstanding = twice the batch
    rows = int(t["rows_per_job"]["of_decode_batch"] * eng["decode_batch_size"])
    assert rows == 16 and t["clients"] * rows == 2 * eng["decode_batch_size"]
    longest = t["prompt_chars"]["long_max"] + 19 + max(t["max_new_tokens_cycle"])
    assert longest == 3579 <= eng["max_model_len"] == eng["prefill_chunk"]
    assert eng["max_pages_per_seq"] * eng["kv_page_size"] >= eng["max_model_len"]
    assert eng["prefill_batch_size"] == 1       # a row a prefill: two buckets
    assert t["output_schema"] is None and t["system_prompt"] is None


def test_rehearsal_of_the_four_lane_cell_traced():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "perfbench/tools/rehearse_added.py", "--cells",
         str(ADDED), "--workload", CELL, "--seed", str(2**31 + 54),
         "--seconds", "8", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # (``moe_expert_rows_max_over_mean`` reads the decode spans INSIDE
    # the 4 s trace: on a loaded CPU the tiny run may have none there)
    assert {"engine_host_us_per_row", "tokens_per_dispatch",
            "decode_row_steps_kept_share",
            "decode_batch_occupancy"} <= set(result["metrics"])
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    # float32 against float32 through the latent cache, by the routed
    # rule: on a CPU no routing flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == 897_376
    # the device metrics are skipped on a CPU, not failed
    for name in ("mhc_mla_moe_decode_hbm_roofline", "mhc_prefill_mxu_roofline",
                 "mhc_stream_hbm_roofline", "mhc_share_of_busy",
                 "mhc_paged_decode_hbm_roofline"):
        assert name not in result["metrics"]
