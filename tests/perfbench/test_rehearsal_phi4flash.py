"""The CPU rehearsal of the cell of a decoder-hybrid-decoder
(``tiny-phi4flash.generate-reasoning-jobs``, entries in
``rehearsal/cells-phi4flash.json``, run by ``tools/rehearse_added.py``
over a copy of the benchmark): the control flow, the numbers check
through ``logits_through_cache`` with ONE table (the state, the window
K/V and the one full layer's K/V riding ``_trunk_decode`` and
``write_kv``), slots and window pages bound and released under traffic,
and the readers this family brings, every one of them under 100 %."""

import json
import os
import subprocess
import sys

from .test_rehearsal import REPO, TAG, result_of

ADDED = REPO / "perfbench/rehearsal/cells-phi4flash.json"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
CELL = "tiny-phi4flash.generate-reasoning-jobs"
REAL = "phi-4-mini-flash-reasoning.generate-reasoning-jobs"
NEW = ("sambay_decode_hbm_roofline", "sambay_paged_decode_hbm_roofline",
       "sambay_state_step_hbm_roofline", "sambay_prefill_mxu_roofline",
       "decode_shared_kv_read_share")


def rehearse(*flags, tmp, timeout=600):
    # the traced run leaves its profile (200 MB) under TMPDIR: pytest's
    # own directory, which it prunes, and not /tmp, which nothing does
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp))
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
    # the five metrics this family brings name the one cell, and the
    # cell joins the slot pool's lists. The six lists whose LAST entry
    # tests/perfbench/test_rehearsal_laguna.py pins wait for a benchmark
    # PR (PERF.md section 7, row 55(h)) and are not asked of here
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [REAL]
        assert by_name[name]["moves"] == "out_tokens_per_s_per_chip"
    for name in ("state_slot_occupancy", "state_fallback_prefill_share"):
        assert REAL in by_name[name]["workloads"]   # (a later cell may follow)
    cell = next(w for w in BENCH["workloads"] if w["name"] == REAL)
    assert cell["chips"] == 1


def test_the_timed_traffic_is_the_issues_table():
    from perfbench.stats import pool_from_spec

    t = json.loads(
        (REPO / "perfbench/traffic/generate-reasoning-jobs.json").read_text())
    long_output = json.loads(
        (REPO / "perfbench/traffic/generate-long-output-jobs.json").read_text())
    assert (t["generator"], t["clients"]) == ("batch_jobs", 32)
    assert t["rows_per_job"] == {"of_decode_batch": 0.0625}
    assert t["prompt_chars"] == long_output["prompt_chars"]
    assert t["max_new_tokens_cycle"] == [768, 1280, 1024, 896, 1152, 1024]
    assert sum(t["max_new_tokens_cycle"]) / 6 == 1024
    assert t["sampling"] == {"temperature": 0.7}
    assert t["output_schema"] is None and t["system_prompt"] is None
    assert t["lead_in_s"] == 20.0 and t["warm"]["max_new_tokens"] == 9
    cfg = json.loads((REPO / "perfbench/configs/"
                      "phi-4-mini-flash-reasoning-v5e1.json").read_text())
    e = cfg["engine"]
    rows = int(0.0625 * e["decode_batch_size"])
    assert rows == 8 and t["clients"] * rows == 2 * e["decode_batch_size"]
    sizes = pool_from_spec(t["prompt_chars"])
    assert (max(sizes) + 19 + max(t["max_new_tokens_cycle"])
            <= e["max_model_len"] == e["prefill_chunk"])

    def bucket(chars):
        b = 16
        while b < chars + 19:
            b *= 2
        return b

    # one row alone at each bucket the prompts meet, then jobs of 2-8
    alone = {bucket(g["chars"]) for g in t["warm"]["groups"] if g["rows"] == 1}
    assert {bucket(n) for n in sizes} == alone == {128, 256, 512, 1024}
    assert sorted(g["rows"] for g in t["warm"]["groups"] if g["rows"] > 1) == [
        2, 3, 4, 5, 6, 7, 8]


def test_rehearsal_of_the_reasoning_cell(tmp_path):
    proc = rehearse(
        "--workload", CELL, "--seed", str(2**31 + 64),
        "--seconds", "8", "--trace", "1", tmp=tmp_path,
    )
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    m = result["metrics"]
    assert {"tokens_per_dispatch", "state_slot_occupancy",
            "state_fallback_prefill_share", "decode_shared_kv_read_share",
            } <= set(m)
    facts = next(
        json.loads(ln[len(TAG):])["facts"] for ln in proc.stdout.splitlines()
        if ln.startswith(TAG + '{"facts"')
    )
    numbers = facts["numbers"]
    # float32 against float32 through the slots, the map and the paged
    # cache, every position held (the dense rule)
    assert numbers["dtype"] == "float32"
    assert numbers["numbers_source"] == "harness"
    assert max(numbers["rel_err_prefill"], numbers["rel_err_decode_max"]) < 2e-4
    # one reader of two reads the other's pool
    assert 20.0 < m["decode_shared_kv_read_share"]["value"] < 50.0
    assert 0.0 < m["state_slot_occupancy"]["value"] <= 100.0
    assert m["state_fallback_prefill_share"]["value"] == 0.0
    # the device metrics are skipped on a CPU, not failed; whatever of
    # the family's is read is a share under 100 %
    for name in NEW[:4]:
        assert name not in m
    for name, got in m.items():
        if got.get("unit") == "%":
            assert got["value"] <= 100.0, name
