"""The README's promise, "new files and new entries, never an edit", for
a routed model: a copy of the benchmark with one more configuration, one
more cell on the traffic ``generate-jobs`` and one more reference family
resolves through ``run.load_cell``, ``run.metrics_for`` and
``correctness.numbers``' choice of rule, and nothing that was there
changed. The copy runs in a process of its own, so that its ``perfbench``
package is the copy's."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DECODE_METRICS = {"engine_host_us_per_row", "decode_step_device_ms",
                  "prefill_device_us_per_token", "decode_hbm_roofline"}

FAMILY = '''"""A reference family the benchmark never had: it routes."""
import numpy as np

ROUTED = True
TIE_MARGIN = 0.5


def logits_and_near_ties(cfg, params, ids, positions):
    rng = np.random.default_rng([int(i) for i in ids][:8])
    logits = rng.standard_normal((len(positions), cfg["vocab_size"]))
    return logits.astype(np.float32), np.ones(len(positions), np.int32)


def logits_at(cfg, params, ids, positions):
    return logits_and_near_ties(cfg, params, ids, positions)[0]
'''

SCRIPT = '''
import json, sys
import numpy as np
from perfbench import correctness, run
from perfbench.reference import new_family

bench = json.loads(open("BENCHMARK.json").read())
cell, cfg = run.load_cell(bench, "new-moe.generate-jobs")


class Exact:
    """A system that gives the family's own logits back."""
    def serving_dtype(self): return "bfloat16"
    def weights(self): return None
    def kernel_paths(self): return {}
    def uses_kernels(self): return False
    def logits_through_cache(self, ids, n_prefill, n_decode):
        at = list(range(n_prefill - 1, n_prefill + n_decode))
        return np.stack([new_family.logits_at(cfg, None, s, at) for s in ids])


problems, facts = correctness.numbers(Exact(), cfg, 2**31 + 3)
print(json.dumps({
    "run_py": run.__file__, "config": cfg["name"], "traffic": cell["traffic"],
    "per_layer": [m["name"] for m in run.metrics_for(bench, cell, "per_layer")],
    "end_to_end": [m["name"] for m in run.metrics_for(bench, cell, "end_to_end")],
    "problems": problems, "facts": facts,
}))
'''


def extends(old, new) -> bool:
    """``new`` holds everything ``old`` held, in place: a dict keeps its
    keys, a list its items in order at its head, a value itself."""
    if isinstance(old, dict):
        return isinstance(new, dict) and all(
            k in new and extends(v, new[k]) for k, v in old.items())
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(extends(a, b) for a, b in zip(old, new)))
    return old == new


def test_a_routed_model_is_added_by_new_files_and_new_entries(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    before = json.loads(json.dumps(bench))
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    kept = {f: f.read_bytes() for f in tmp_path.rglob("*") if f.is_file()}

    # what a model_config PR brings: two files ...
    template = json.loads((tmp_path / "perfbench/configs/qwen3-4b-v5e1.json").read_text())
    config = dict(
        template, name="new-moe-v5e1", reference="new_family",
        source="https://example.org/new-moe/config.json", num_experts=64,
        num_experts_per_tok=6, moe_intermediate_size=512, norm_topk_prob=True,
        numbers={"sequences": 5, "quantile": 0.3, "cap": 0.25,
                 "why": "set from chip readings by the PR that adds it"},
    )
    (tmp_path / "perfbench/configs/new-moe-v5e1.json").write_text(json.dumps(config))
    (tmp_path / "perfbench/reference/new_family.py").write_text(FAMILY)
    # ... and entries: a configuration, a cell, and the cell's name on the
    # metrics that only some cells can read
    bench["configs"].append({
        "name": "new-moe-v5e1", "source": config["source"],
        "file": "perfbench/configs/new-moe-v5e1.json", "reduced": [],
        "why": "a routed model"})
    bench["workloads"].append({
        "name": "new-moe.generate-jobs", "config": "new-moe-v5e1",
        "traffic": "generate-jobs", "chips": 1, "why": "routed decode"})
    for m in bench["per_layer"]:
        if m["name"] in DECODE_METRICS:
            m["workloads"].append("new-moe.generate-jobs")
    for m in bench["end_to_end"]:
        if m["name"] == "out_tokens_per_s_per_chip":
            m["workloads"].append("new-moe.generate-jobs")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    assert extends(before, bench) and not extends(bench, before)
    for f, data in kept.items():
        assert f.read_bytes() == data, f"{f} was edited"

    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], cwd=tmp_path, capture_output=True,
        text=True, timeout=120, env={"PYTHONPATH": str(tmp_path), "PATH": ""},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = json.loads(proc.stdout.splitlines()[-1])
    assert Path(seen["run_py"]).is_relative_to(tmp_path)
    assert (seen["config"], seen["traffic"]) == ("new-moe-v5e1", "generate-jobs")
    assert DECODE_METRICS <= set(seen["per_layer"])
    # and every metric that names no cell, none that names another's
    named_elsewhere = {m["name"] for m in before["per_layer"]
                       if "workloads" in m and m["name"] not in DECODE_METRICS}
    unnamed = {m["name"] for m in before["per_layer"] if "workloads" not in m}
    assert unnamed <= set(seen["per_layer"])
    assert not named_elsewhere & set(seen["per_layer"])
    assert set(seen["end_to_end"]) == {"out_tokens_per_s_per_chip", "setup_s"}
    # the family says it routes, so the configuration's own numbers rule it
    assert seen["problems"] == []
    facts = seen["facts"]
    assert (facts["rule"], facts["sequences"], facts["positions"]) == ("routed", 5, 45)
    assert (facts["quantile"], facts["cap"], facts["tolerance"]) == (0.3, 0.25, 0.06)
    assert facts["near_tie_margin"] == 0.5 and facts["near_ties_mean"] == 1.0
    assert facts["rel_err_max"] == 0.0
