"""``bytes_and_flops_kda.py`` against the weights the program builds and
against ISSUE 50's inventory, and the five readers that a model of
delta-rule (KDA) layers brings, on hand-made readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops_kda as kd
from perfbench.layer_metrics import (
    kda_gqa_moe_decode_hbm_roofline, kda_prefill_mxu_roofline,
    kda_state_bytes_moved_over_needed, kda_state_commit_hbm_roofline,
    kda_state_read_hbm_roofline,
)
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import state_bytes_per_slot
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(kd.__file__).parent
CUT = json.loads((
    PERFBENCH / "configs/solar-open2-250b-l8-ep16-v5e1.json"
).read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-solar-kda-cpu.json").read_text()
)
BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELL = "solar-open2-250b-l8-ep16.generate-long-output-jobs"
READERS = (
    kda_gqa_moe_decode_hbm_roofline, kda_prefill_mxu_roofline,
    kda_state_bytes_moved_over_needed, kda_state_read_hbm_roofline,
    kda_state_commit_hbm_roofline,
)


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert kd.param_count(cfg) == served(cfg["engine_key"]) == cfg["parameters"]


def test_the_cut_is_the_issues_inventory():
    d = kd.dims(CUT)
    assert (d["kda_layers"], d["attn_layers"], d["L"]) == (6, 2, 8)
    assert (d["E_held"], d["E_router"], d["top_k"]) == (20, 320, 8)
    assert kd.kda_mixer_params(d) == 137_740_480
    assert kd.attention_mixer_params(d) == 109_051_904
    assert kd.expert_params(d) == 15_728_640
    assert kd.ffn_params(d, 0) == 17_047_872
    assert kd.param_count(CUT) == (
        6 * 469_361_152 + 2 * 440_672_576 + 2 * 24_576 * 4_096 + 4_096
    ) == 3_898_842_752
    assert 7.79e9 < 2 * kd.param_count(CUT) < 7.81e9
    # the whole published model: the catalog's 250B-A15B
    assert kd.published_param_count(CUT) == CUT["published"]["parameters"] == (
        served("solar-open2-250b")) == 250_288_105_216
    assert kd.active_param_count(CUT, published=True) == (
        CUT["published"]["parameters_a_token"]) == 14_735_992_576


def test_the_file_states_the_cut_and_changes_no_width():
    assert CUT["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "gqa_layers"]
    pub = CUT["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 320, 196_608)
    assert pub["gqa_layers"][:2] == CUT["gqa_layers"] == [0, 4]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.open()
                   if '"Solar-Open2-250B"' in line)
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
            assert pub.get(key, value) == value, key
        assert CUT["source"] == row["source_url"]
    for key in ("kda_use_full_proj", "kda_allow_neg_eigval", "decay",
                "gate_bias", "use_gqa_gate", "router", "weights",
                "state_dtype", "mtp"):
        assert CUT["assumed"][key]
    share = CUT["share"]
    assert (share["chips_per_layer"], share["rank"], share["experts_published"],
            share["first_expert"], share["experts_held"]) == (16, 0, 320, 0, 20)
    entry = next(c for c in BENCH["configs"] if c["name"] == CUT["name"])
    assert entry["reduced"] == CUT["reduced"] and entry["source"] == CUT["source"]
    m = MODEL_CONFIGS[CUT["engine_key"]]
    assert (m.hidden_size, m.moe_intermediate_size,
            m.moe_shared_intermediate_size) == (4096, 1280, 1280)
    assert (m.kda_heads, m.kda_head_dim, m.kda_conv, m.kda_rank) == (
        64, 128, 4, 128)
    assert (m.router_scale, m.router_renorm_eps, m.norm_eps) == (1.0, 1e-20, 1e-5)


def test_state_and_kv_a_token_are_the_programs():
    m = MODEL_CONFIGS[CUT["engine_key"]]
    ecfg = EngineConfig(**CUT["engine"])
    assert kd.state_bytes_per_sequence(CUT) == state_bytes_per_slot(m, ecfg)
    assert kd.state_matrix_bytes_per_sequence(CUT) == 6 * 64 * 128 * 128 * 2
    assert kd.state_bytes_per_sequence(CUT) == 6 * (128 * 8192 + 3 * 24576) * 2
    # two GQA layers x K and V x 8 heads x 128, bf16: a page row of 1,024
    assert kd.kv_bytes_per_token(CUT) == 2 * 2 * 1024 * 2 == 8192


def test_a_decode_step_is_the_issues_eleven_gigabytes():
    got = kd.decode_bytes_per_step(
        CUT, batch=192, mean_ctx=1000, state_rows=192, experts_touched=20,
        steps_per_commit=8)
    assert 11.5e9 < got < 12.1e9
    state = 192 * 12_582_912
    # the state once a step, its write a window's eighth
    once = kd.decode_bytes_per_step(
        CUT, batch=192, mean_ctx=1000, state_rows=192, experts_touched=20,
        steps_per_commit=1)
    assert once - got == pytest.approx(state * (1 - 1 / 8))
    assert 0.19 < state / got < 0.25
    fewer = kd.decode_bytes_per_step(
        CUT, batch=192, mean_ctx=1000, state_rows=192, experts_touched=10,
        steps_per_commit=8)
    assert got - fewer == pytest.approx(8 * 10 * 15_728_640 * 2)


ATTRS = {"steps": 8, "batch": 190, "avg_ctx": 500, "state_rows": 190,
         "state_bytes": 190 * 13_467_648, "kda_state_bytes": 190 * 12_582_912,
         "experts_touched": 19.5, "expert_rows_max": 12.0,
         "expert_rows_mean": 4.8}


def test_the_roofline_reads_the_spans_and_the_counts():
    got = kda_gqa_moe_decode_hbm_roofline.read(
        reading(CUT, [ATTRS, ATTRS], step_s=0.03))
    want = kd.decode_bytes_per_step(
        CUT, batch=190, mean_ctx=500, state_rows=190, experts_touched=19.5,
        steps_per_commit=8)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.03)
    assert 40.0 < got < 50.0
    for gone in ("kda_state_bytes", "experts_touched"):
        bare = {k: v for k, v in ATTRS.items() if k != gone}
        assert kda_gqa_moe_decode_hbm_roofline.read(reading(CUT, [bare])) is None


def test_each_kernels_roofline_reads_its_ops_and_the_spans():
    r = reading(CUT, [ATTRS, ATTRS], step_s=0.03)
    r.trace["op_s"] = {"fusion": 1.0}       # the XLA forms ran: nothing
    assert kda_state_read_hbm_roofline.read(r) is None
    assert kda_state_commit_hbm_roofline.read(r) is None
    r.trace["op_s"] = {"kda_state_read": 0.1, "kda_state_commit": 0.02,
                       "fusion": 1.0}
    per_step = 190 * 12_582_912
    # two runs of the decode program x 8 steps read; two commits, in and out
    assert kda_state_read_hbm_roofline.read(r) == pytest.approx(
        100.0 * 16 * per_step / 819e9 / 0.1)
    assert kda_state_commit_hbm_roofline.read(r) == pytest.approx(
        100.0 * 2 * 2 * per_step / 819e9 / 0.02)
    assert kda_state_read_hbm_roofline.read(r) < 100.0
    assert kda_state_commit_hbm_roofline.read(r) < 100.0


def test_the_prefill_share_reads_the_rows_own_lengths():
    r = reading(CUT, [ATTRS])
    assert kda_prefill_mxu_roofline.read(r) is None        # no prefill ran
    r.spans.append(("prefill", 3.0, 3.1, {"tokens": 300}))
    r.spans.append(("prefill", 3.2, 3.3, {"tokens": 0, "wave": 1}))
    r.trace["module_s"]["jit__prefill_jit"] = {"s": 0.05, "runs": 1.0}
    want = kd.prefill_flops_per_row(CUT, 300) / 197e12
    assert kda_prefill_mxu_roofline.read(r) == pytest.approx(100.0 * want / 0.05)
    assert kda_prefill_mxu_roofline.read(r) < 100.0


def test_moved_over_needed_reads_the_counters():
    moved, needed = (kda_state_bytes_moved_over_needed.MOVED,
                     kda_state_bytes_moved_over_needed.NEEDED)
    reg0 = {moved: {"series": {"read": 10.0, "commit": 5.0}},
            needed: {"series": {"": 9.0}}}
    reg1 = {moved: {"series": {"read": 90.0, "commit": 25.0}},
            needed: {"series": {"": 99.0}}}
    r = reading(CUT, [ATTRS], registry=(reg0, reg1))
    assert kda_state_bytes_moved_over_needed.read(r) == pytest.approx(100 / 90)


def test_a_program_or_a_configuration_without_the_layers_reads_nothing():
    """The parent's program (no span attr, no counter, no op) and another
    family's configuration: every reader returns None, none raises."""
    nemotron = json.loads((
        PERFBENCH / "configs/nemotron-3-nano-30b-a3b-l14-ep2-v5e1.json"
    ).read_text())
    bare = {"steps": 8, "batch": 190, "avg_ctx": 500}
    for cfg, attrs in ((CUT, bare), (nemotron, ATTRS)):
        r = reading(cfg, [attrs])
        r.trace["op_s"] = {"fusion": 1.0}
        for mod in READERS:
            assert mod.read(r) is None, (mod.__name__, cfg["name"])
    r = reading(CUT, [ATTRS])
    r.trace = None
    for mod in READERS:
        assert mod.read(r) is None, mod.__name__


def test_the_cell_is_listed_where_its_readers_find_something():
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "engine_host_us_per_row", "decode_step_device_ms",
        "prefill_device_us_per_token", "decode_row_steps_kept_share",
        "moe_expert_rows_max_over_mean", "state_fallback_prefill_share",
        "state_slot_occupancy",
    } | {mod.__name__.rsplit(".", 1)[1] for mod in READERS}
    for m in BENCH["per_layer"]:
        if m["name"].startswith("kda_"):
            assert CELL in m["workloads"]
            assert m["moves"] == "out_tokens_per_s_per_chip"
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "out_tokens_per_s_per_chip")
    assert CELL in e2e["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "generate-long-output-jobs"
    assert cell["config"] == CUT["name"]
