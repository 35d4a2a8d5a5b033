"""The CPU rehearsal of the cell of a model whose layer kinds differ in
their query heads, with short and long rows in one queue
(``tiny-laguna.generate-mixed-length-jobs``, entries in
``rehearsal/cells-laguna.json``, run by ``tools/rehearse_added.py`` over a
copy of the benchmark): the control flow, the numbers check through
``logits_through_cache`` with ONE table and the identity map, window
pages bound and released under traffic, batched prefills padded to their
longest, and the readers this family brings, every one of them under
100 %."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-laguna.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-laguna.generate-mixed-length-jobs"
REAL = "laguna-s-2.1-l9-ep8.generate-mixed-length-jobs"
NEW = ("laguna_moe_decode_hbm_roofline", "laguna_paged_decode_hbm_roofline",
       "laguna_prefill_mxu_roofline", "prefill_padded_token_share")


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
    # the four metrics this family brings name the one cell, and the
    # cell joins the lists a generate cell over two pools and a held
    # share reads
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [REAL]
        assert by_name[name]["moves"] == "out_tokens_per_s_per_chip"
    for name in ("window_kv_held_share", "moe_rows_held_share",
                 "decode_kv_pages_fetched_over_needed",
                 "moe_expert_rows_max_over_mean", "decode_step_device_ms",
                 "prefill_device_us_per_token", "engine_host_us_per_row",
                 "decode_row_steps_kept_share"):
        assert by_name[name]["workloads"][-1] == REAL


def test_the_timed_traffic_is_the_issues_table():
    from perfbench.stats import pool_from_spec

    t = json.loads(
        (REPO / "perfbench/traffic/generate-mixed-length-jobs.json").read_text())
    assert (t["generator"], t["clients"]) == ("batch_jobs", 16)
    assert t["rows_per_job"] == {"of_decode_batch": 0.125}
    assert t["prompt_chars"] == {
        "pool": 32, "pool_seed": 20261004, "median": 330, "sigma": 0.45,
        "min": 48, "max": 672, "long_every": 4, "long_min": 2600,
        "long_max": 7400}
    assert t["max_new_tokens_cycle"] == [192, 320, 256, 224, 288, 256]
    assert t["sampling"] == {"temperature": 0.7}
    assert t["output_schema"] is None and t["system_prompt"] is None
    assert t["lead_in_s"] == 20.0
    cfg = json.loads((REPO / "perfbench/configs/"
                      "laguna-s-2.1-l9-ep8-v5e1.json").read_text())
    e = cfg["engine"]
    # a job is the pool's first 16 sizes: 12 short and 4 long rows, the
    # same in every job and every seed; every prompt is one chunk
    job = pool_from_spec(t["prompt_chars"])[: int(0.125 * e["decode_batch_size"])]
    long_rows = [n for n in job if n >= 2600]
    assert len(job) == 16 and sorted(long_rows) == [2638, 4428, 6037, 7079]
    assert max(n for n in job if n < 2600) == 672
    assert (max(job) + 19 + max(t["max_new_tokens_cycle"])
            <= e["max_model_len"] == e["prefill_chunk"])
    # the warm groups meet every bucket the sizes meet, a row alone
    def bucket(chars):
        b = 16
        while b < chars + 19:
            b *= 2
        return b

    alone = {bucket(g["chars"]) for g in t["warm"]["groups"] if g["rows"] == 1}
    assert {bucket(n) for n in job} == alone == {256, 512, 1024, 4096, 8192}
    assert cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]


@pytest.mark.parametrize("trace,expect", [
    (1, {"engine_host_us_per_row", "tokens_per_dispatch",
         "decode_kv_pages_fetched_over_needed", "window_kv_held_share",
         "decode_batch_occupancy", "prefill_padded_token_share",
         "moe_expert_rows_max_over_mean", "moe_rows_held_share"}),
])
def test_rehearsal_of_the_mixed_length_cell(trace, expect):
    proc = rehearse(
        "--workload", CELL, "--seed", str(2**31 + 61),
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
    if trace:
        m = result["metrics"]
        # a window of 8: pages went back; rows of 25-200 tokens in
        # batches of four padded to their longest
        assert 0.0 < m["window_kv_held_share"]["value"] < 0.8
        assert 0.0 < m["prefill_padded_token_share"]["value"] < 1.0
        # 4 of 16 experts held; the warm groups meet every shape the
        # closed loop admits (a whole job of four rows at the long
        # row's bucket), so no compile takes the traced seconds from
        # the decode windows that carry the routing's counts
        assert 0.0 < m["moe_rows_held_share"]["value"] < 1.0
        assert m["moe_expert_rows_max_over_mean"]["value"] >= 1.0
        # the device metrics are skipped on a CPU, not failed; whatever
        # of the family's is read is a share under 100 %
        for name in NEW[:3]:
            assert name not in m
        for name, got in m.items():
            if got.get("unit") == "%":
                assert got["value"] <= 100.0, name
