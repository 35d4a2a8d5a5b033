"""The CPU rehearsal of the cell of a model that generates by diffusion
over blocks (``tiny-sdar.generate-block-diffusion-jobs``, entries in
``rehearsal/cells-sdar.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the routed numbers check
through the program's own forced forward against the plain reference,
and the readers this family brings."""

import json
import os
import subprocess
import sys

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-sdar.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-sdar.generate-block-diffusion-jobs"


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


def test_the_timed_cells_traffic_fits_its_engine():
    t = json.loads((REPO / "perfbench/traffic/"
                    "generate-block-diffusion-jobs.json").read_text())
    cfg = json.loads((REPO / "perfbench/configs/"
                      "sdar-30b-a3b-chat-l6-v5e1.json").read_text())
    eng = cfg["engine"]
    # a sixteenth of the batch a job: 8 rows, 256 outstanding = twice the batch
    rows = int(t["rows_per_job"]["of_decode_batch"] * eng["decode_batch_size"])
    assert rows == 8 and t["clients"] * rows == 2 * eng["decode_batch_size"]
    longest = t["prompt_chars"]["long_max"] + 19 + max(t["max_new_tokens_cycle"])
    assert longest <= eng["max_model_len"] == eng["prefill_chunk"]
    assert eng["prefill_batch_size"] == 1
    assert t["output_schema"] is None and t["system_prompt"] is None
    assert 1 <= t["sampling"]["denoising_steps"] <= cfg["block_length"]


def test_rehearsal_of_the_block_diffusion_cell_traced():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "perfbench/tools/rehearse_added.py", "--cells",
         str(ADDED), "--workload", CELL, "--seed", str(2**31 + 57),
         "--seconds", "8", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert {"engine_host_us_per_row", "tokens_per_dispatch",
            "decode_row_steps_kept_share", "decode_batch_occupancy",
            "bd_forwards_per_token"} <= set(result["metrics"])
    # two denoising forwards and a commit for four positions, and what
    # caps inside a block and prompts' leftover tokens cost on top
    assert 0.75 <= result["metrics"]["bd_forwards_per_token"]["value"] < 1.6
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    # float32 against float32 through the paged cache by the program's
    # own forced forward, by the routed rule: on a CPU no routing flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["numbers_source"] == "forced_logits"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == 872_512
    # the device metrics are skipped on a CPU, not failed
    for name in ("bd_moe_decode_hbm_roofline", "bd_paged_decode_hbm_roofline",
                 "bd_prefill_mxu_roofline", "bd_sample_share_of_step"):
        assert name not in result["metrics"]
