"""The CPU rehearsal of the cell of a model whose every layer is latent
attention over one latent page pool and whose experts are one rank's
share (``tiny-joyai.generate-long-prompt-jobs``, entries in
``rehearsal/cells-joyai.json``, run by ``tools/rehearse_added.py`` over
a copy of the benchmark): the control flow, the routed numbers check
through the latent cache against a reference given the same share, and
the readers the cell lists."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-joyai.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-joyai.generate-long-prompt-jobs"
TIMED = "joyai-llm-flash-ep16.generate-long-prompt-jobs"


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


def test_the_timed_cell_takes_the_long_prompt_traffic_as_it_stands():
    """No new traffic file: the Mellum 2 cell's, at this cell's batch."""
    t = json.loads(
        (REPO / "perfbench/traffic/generate-long-prompt-jobs.json").read_text())
    cfg = json.loads((REPO / "perfbench/configs/"
                      "joyai-llm-flash-ep16-v5e1.json").read_text())
    eng = cfg["engine"]
    assert (t["generator"], t["clients"]) == ("batch_jobs", 16)
    # an eighth of the decode batch a job: 4 rows, 64 outstanding
    rows = int(t["rows_per_job"]["of_decode_batch"] * eng["decode_batch_size"])
    assert rows == 4 and t["clients"] * rows == 2 * eng["decode_batch_size"]
    assert t["max_new_tokens_cycle"] == [96, 160, 128, 112, 144, 128]
    assert t["sampling"] == {"temperature": 0.7}
    assert t["output_schema"] is None and t["system_prompt"] is None
    # the longest row is 3,579 tokens of a context of 4,096, and no
    # prompt is over the prefill chunk: every prompt goes in expanded
    longest = t["prompt_chars"]["long_max"] + 19 + max(t["max_new_tokens_cycle"])
    assert longest == 3579 <= eng["max_model_len"] == eng["prefill_chunk"]
    assert eng["max_pages_per_seq"] * eng["kv_page_size"] >= eng["max_model_len"]
    assert eng["prefill_batch_size"] == 1
    cell = next(w for w in BENCH["workloads"] if w["name"] == TIMED)
    assert cell["traffic"] == t["name"] and cell["chips"] == 1
    mellum = next(w for w in BENCH["workloads"]
                  if w["name"].startswith("mellum2"))
    assert mellum["traffic"] == cell["traffic"]


@pytest.mark.parametrize("trace,expect", [
    (0, {"out_tokens_per_s_per_chip", "setup_s"}),
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "moe_expert_rows_max_over_mean", "decode_row_steps_kept_share",
         "decode_batch_occupancy"}),
])
def test_rehearsal_of_the_latent_cell(trace, expect):
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
    # float32 against float32 through the latent cache (prefill expanded,
    # the eight steps absorbed), by the routed rule: on a CPU no routing
    # flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == 623_536
    if trace:
        assert result["metrics"]["moe_expert_rows_max_over_mean"]["value"] >= 1.0
        # every listed reader was read on this line; the device metrics
        # are skipped on a CPU, not failed
        for name in ("mla_moe_decode_hbm_roofline", "mla_prefill_mxu_roofline",
                     "decode_kv_pages_fetched_over_needed",
                     "state_fallback_prefill_share",
                     # its list is the Nemotron cell's alone, by a test
                     # of the accepted benchmark (PERF.md section 7 row 31)
                     "moe_rows_held_share"):
            assert name not in result["metrics"]
