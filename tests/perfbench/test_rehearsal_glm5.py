"""The CPU rehearsal of the cell of a model whose every layer is latent
attention under an indexer's selection over a latent pool and an
index-key pool, and whose experts are one rank's share
(``tiny-glm-dsa.generate-long-doc-jobs``, entries in
``rehearsal/cells-glm5.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the routed numbers check
through both pools against a reference given the same share, the traffic
file the timed cell brings, and the readers the cell lists."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-glm5.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-glm-dsa.generate-long-doc-jobs"
TIMED = "glm-5-l5-ep16.generate-long-doc-jobs"


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


def test_the_timed_cells_traffic_is_past_index_topk_in_every_row():
    t = json.loads(
        (REPO / "perfbench/traffic/generate-long-doc-jobs.json").read_text())
    cfg = json.loads(
        (REPO / "perfbench/configs/glm-5-l5-ep16-v5e1.json").read_text())
    eng = cfg["engine"]
    assert (t["generator"], t["clients"]) == ("batch_jobs", 16)
    # an eighth of the decode batch a job: 2 rows, 32 outstanding
    rows = int(t["rows_per_job"]["of_decode_batch"] * eng["decode_batch_size"])
    assert rows == 2 and t["clients"] * rows == 2 * eng["decode_batch_size"]
    assert t["max_new_tokens_cycle"] == [192, 320, 256, 224, 288, 256]
    assert t["sampling"] == {"temperature": 0.7}
    assert t["output_schema"] is None and t["system_prompt"] is None
    p = t["prompt_chars"]
    # the byte tokenizer adds 19 template tokens: every prompt is past
    # index_topk (no attention in the window is dense), the common ones
    # inside [1, 8192], every eighth inside [1, 16384]
    assert p["min"] + 19 > cfg["index_topk"] == 2048
    assert p["max"] + 19 <= 8192 < p["long_min"] + 19
    longest = p["long_max"] + 19 + max(t["max_new_tokens_cycle"])
    assert longest == 15_839 <= eng["max_model_len"] == eng["prefill_chunk"]
    assert eng["max_pages_per_seq"] * eng["kv_page_size"] >= eng["max_model_len"]
    assert eng["prefill_batch_size"] == 1 and (p["long_every"], p["pool"]) == (8, 32)
    # what a job holds: the pool's FIRST rows_per_job sizes
    # (generators/batch_jobs.py): both inside [1, 8192]; the every-eighth
    # long sizes sit past a 2-row job's reach (PERF.md section 6, PR 46)
    from perfbench.stats import pool_from_spec

    pool = pool_from_spec(p)
    assert pool[:rows] == [6260, 7420]
    assert [i for i, n in enumerate(pool) if n >= p["long_min"]] == [7, 15, 23, 31]
    # a warm row at each bucket, and one at or under index_topk so that
    # the flash body is lowered by the model's own short path
    warm = sorted({g["chars"] for g in t["warm"]["groups"]})
    assert warm == [1500, 6400, 14000]
    cell = next(w for w in BENCH["workloads"] if w["name"] == TIMED)
    assert cell["traffic"] == t["name"] and cell["chips"] == 1


@pytest.mark.parametrize("trace,expect", [
    (0, {"out_tokens_per_s_per_chip", "setup_s"}),
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "moe_expert_rows_max_over_mean", "decode_row_steps_kept_share",
         "decode_batch_occupancy", "sparse_kv_rows_read_share"}),
])
def test_rehearsal_of_the_selecting_cell(trace, expect):
    proc = rehearse(
        "--workload", CELL, "--seed", str(2**31 + 17),
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
    # float32 against float32 through both pools (an index_topk of 8 and
    # 192 + 8 positions: the selection bites at every one), by the routed
    # rule: on a CPU no routing and no selection flips
    assert numbers["rule"] == "routed" and numbers["dtype"] == "float32"
    assert numbers["rel_err_max"] < 2e-4
    assert facts["params"]["served"] == 436_560
    if trace:
        share = result["metrics"]["sparse_kv_rows_read_share"]["value"]
        assert 0.0 < share < 0.5          # 8 rows of contexts of 40-160
        # every listed reader was read on this line; the device metrics
        # are skipped on a CPU, not failed
        for name in ("dsa_moe_decode_hbm_roofline", "dsa_prefill_mxu_roofline",
                     "mla_moe_decode_hbm_roofline", "mla_prefill_mxu_roofline",
                     "decode_kv_pages_fetched_over_needed",
                     "moe_rows_held_share"):
            assert name not in result["metrics"]
