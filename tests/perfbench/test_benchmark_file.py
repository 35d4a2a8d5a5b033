"""BENCHMARK.json against the contract's limits, and against the files
it names: every name and unit, every cell's files, every metric's
reader. Also that run.py is driven by data."""

import importlib
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
REHEARSAL = json.loads((REPO / "perfbench/rehearsal/cells.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E, LAYER = BENCH["end_to_end"], BENCH["per_layer"]
CELLS = BENCH["workloads"]


def test_top_level_keys_and_size():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    # a full check with all 24 cells fits the driver's 43,200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_command_and_paths():
    assert 1 <= len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and (REPO / p).is_dir()
    script = [w for w in BENCH["command"] if w.endswith(".py")]
    assert script and all(
        any(w.startswith(p + "/") for p in BENCH["paths"]) for w in script
    )


def test_files_under_paths_have_plain_names():
    for p in BENCH["paths"]:
        for f in (REPO / p).rglob("*"):
            if "__pycache__" in f.parts or f.is_dir():
                continue
            rel = f.relative_to(REPO).as_posix()
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


@pytest.mark.parametrize("m", E2E + LAYER, ids=lambda m: m["name"])
def test_metric_entry(m):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if m in E2E else {"layer", "moves"}
    assert set(m) <= allowed and {"name", "unit", "better", "source"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {c["name"] for c in CELLS}
    assert set(m.get("workloads", cells)) <= cells
    if m in E2E:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        target = next(e for e in E2E if e["name"] == m["moves"])
        # the metric it moves is reported wherever this one is; one that
        # lists no cell goes wherever the metric it moves goes
        goes = set(target.get("workloads", cells))
        assert set(m.get("workloads", goes)) <= goes
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_names_are_unique_and_counts_in_range():
    for group, most in ((E2E, 16), (LAYER, 128), (CELLS, 24), (BENCH["configs"], 24)):
        names = [x["name"] for x in group]
        assert 1 <= len(names) <= most and len(set(names)) == len(names)
    assert len({m["name"] for m in E2E + LAYER}) == len(E2E + LAYER)
    assert "setup_s" in {m["name"] for m in E2E}


WAITING = REHEARSAL.get("end_to_end", []) + REHEARSAL.get("per_layer", [])


@pytest.mark.parametrize("m", E2E + LAYER + WAITING, ids=lambda m: m["name"])
def test_metric_has_a_reader_that_agrees(m):
    package = "layer_metrics" if "layer" in m else "e2e_metrics"
    mod = importlib.import_module(f"perfbench.{package}.{m['name']}")
    assert callable(mod.read)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (m["unit"], m["better"], m["source"])
    if "layer" in m:
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])


@pytest.mark.parametrize(
    "c,cells",
    [(c, CELLS) for c in BENCH["configs"]]
    + [(c, REHEARSAL["workloads"]) for c in REHEARSAL["configs"]],
    ids=lambda x: x["name"] if "file" in x else "",
)
def test_config_entry_and_file(c, cells):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
    assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    doc = json.loads((REPO / c["file"]).read_text())
    assert doc["name"] == c["name"]
    # a rehearsal file's source goes on to say it is no published model
    assert doc["source"] == c["source"] or (
        cells is not CELLS and doc["source"].startswith(c["source"]))
    assert doc["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    assert any(w["config"] == c["name"] for w in cells)
    from perfbench.sut import config_field_names

    assert set(doc["engine"]) <= config_field_names()
    from sutro_tpu.models.configs import MODEL_CONFIGS

    m = MODEL_CONFIGS[doc["engine_key"]]
    # the file's published sizes are the ones the program serves
    assert (m.hidden_size, m.num_layers, m.num_heads, m.num_kv_heads,
            m.head_dim, m.intermediate_size, m.vocab_size, m.tie_embeddings) == (
        doc["hidden_size"], doc["num_hidden_layers"], doc["num_attention_heads"],
        doc["num_key_value_heads"], doc["head_dim"], doc["intermediate_size"],
        doc["vocab_size"], doc["tie_word_embeddings"])
    # and the routed ones, when the file has them; a file that routes
    # says how its numbers are checked, a dense one does not
    assert (m.moe_experts, m.moe_top_k) == (
        int(doc.get("num_experts") or 0), int(doc.get("num_experts_per_tok") or 0))
    if m.moe_experts:
        from perfbench import correctness

        assert m.moe_intermediate_size == doc["moe_intermediate_size"]
        assert doc["norm_topk_prob"] is True     # ops/moe.py renormalises
        assert doc["reference"] != "qwen3_dense"
        assert correctness.routed_spec(doc)["sequences"] >= 4
    else:
        assert "numbers" not in doc


@pytest.mark.parametrize(
    "cell,traffic_dir",
    [(w, "perfbench/traffic") for w in CELLS]
    + [(w, "perfbench/rehearsal/traffic") for w in REHEARSAL["workloads"]],
    ids=lambda x: x["name"] if isinstance(x, dict) else "",
)
def test_cell_resolves_to_files_that_exist(cell, traffic_dir):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    pending = [cell["traffic"]]
    while pending:
        traffic = json.loads((REPO / traffic_dir / f"{pending.pop()}.json").read_text())
        importlib.import_module("perfbench.generators." + traffic["generator"])
        pending += [p["traffic"] for p in traffic.get("parts", [])]


def test_cells_cover_configs_once_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in CELLS]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in CELLS:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        doc = json.loads((REPO / configs[w["config"]]["file"]).read_text())
        assert doc["chips"] == w["chips"]
    four = sum(1 for w in CELLS if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS, ids=lambda w: w["name"])
def test_every_cell_reports_enough(cell):
    from perfbench import run

    e2e = [m["name"] for m in run.metrics_for(BENCH, cell, "end_to_end")]
    layer = run.metrics_for(BENCH, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(layer) >= 1 and all(m["moves"] in e2e for m in layer)
    # setup_s aside, something moves every end-to-end metric of the cell
    assert set(e2e) - {"setup_s"} <= {m["moves"] for m in layer}


def test_run_py_names_no_model_cell_or_metric():
    text = (REPO / "perfbench/run.py").read_text()
    words = [m["name"] for m in E2E + LAYER] + [w["name"] for w in CELLS]
    words += [c["name"] for c in BENCH["configs"]] + ["qwen", "tiny-dense"]
    words += [w["name"] for w in REHEARSAL["workloads"]]
    assert [w for w in words if w in text] == []


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in LAYER}
    assert len({l.lower() for l in layers}) == len(layers)
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf
