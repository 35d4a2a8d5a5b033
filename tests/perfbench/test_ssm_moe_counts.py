"""``bytes_and_flops_ssm_moe.py`` against the weights the program builds
and against ISSUE 40's inventory, and the three readers that a model of
one-sublayer blocks with a held share of experts brings, on hand-made
readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops_ssm_moe as sm
from perfbench.layer_metrics import (
    grouped_matmul_decode_hbm_roofline, moe_rows_held_share,
    ssm_moe_decode_hbm_roofline,
)
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import state_bytes_per_slot
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(sm.__file__).parent
CUT = json.loads((
    PERFBENCH / "configs/nemotron-3-nano-30b-a3b-l14-ep2-v5e1.json"
).read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-nemotron-h-cpu.json").read_text()
)
BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELL = "nemotron-3-nano-30b-a3b-l14.generate-long-output-jobs"


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert sm.param_count(cfg) == served(cfg["engine_key"]) == cfg["parameters"]


def test_the_cut_is_the_issues_inventory():
    d = sm.dims(CUT)
    assert (d["mamba_blocks"], d["moe_blocks"], d["attn_blocks"]) == (6, 6, 2)
    assert (d["E_held"], d["E_router"], d["top_k"]) == (64, 128, 6)
    assert sm.mamba_block_params(d) == 38_744_896
    assert sm.attention_block_params(d) == 23_399_040
    assert sm.expert_params(d) == 9_977_856
    assert sm.routed_block_params(d) == 658_885_376
    assert sm.param_count(CUT) == (
        6 * 38_744_896 + 2 * 23_399_040 + 6 * 658_885_376
        + 2 * 65_536 * 2_688 + 2_688
    ) == 4_584_903_936
    assert 9.16e9 < 2 * sm.param_count(CUT) < 9.18e9
    # the whole published model: 52 blocks, every expert, the whole
    # vocabulary: the catalog's 31.6 B
    pub = dict(CUT, **{k: CUT["published"][k] for k in CUT["reduced"]})
    assert sm.param_count(pub) == CUT["published"]["parameters"] == served(
        "nemotron-3-nano-30b-a3b")
    assert 31.5e9 < sm.param_count(pub) < 31.7e9
    # one token on this chip: 3 of its 6 experts a routed block, on average
    assert sm.active_param_count(CUT) == int(
        sm.param_count(CUT) - 6 * 61 * 9_977_856 - 65_536 * 2_688
    )


def test_the_file_states_the_cut_and_changes_no_width():
    assert CUT["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    pub = CUT["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (52, 128, 131_072)
    assert pub["hybrid_override_pattern"].startswith(
        CUT["hybrid_override_pattern"])
    assert CUT["hybrid_override_pattern"] == "MEMEM*E" * 2
    assert len(pub["hybrid_override_pattern"]) == 52
    row = next(
        json.loads(line) for line in
        Path("/opt/skills/guides/model-configs/architectures.jsonl").open()
        if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line
    ) if Path("/opt/skills/guides/model-configs/architectures.jsonl").exists() \
        else None
    if row is not None:
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
        assert CUT["source"] == row["source_url"]
    for key in ("position_embedding", "mamba_inner", "weights", "tokenizer",
                "decode_batch_size", "prefill_batch_size", "attention"):
        assert CUT["assumed"][key]
    entry = next(c for c in BENCH["configs"] if c["name"] == CUT["name"])
    assert entry["reduced"] == CUT["reduced"] and entry["source"] == CUT["source"]
    # the preset is the file's model
    m = MODEL_CONFIGS[CUT["engine_key"]]
    assert (m.hidden_size, m.moe_intermediate_size,
            m.moe_shared_intermediate_size) == (2688, 1856, 3712)
    assert (m.moe_experts, m.experts_held, m.moe_first_expert, m.moe_top_k) == (
        128, 64, 0, 6)
    assert (m.mamba_heads, m.mamba_head_dim, m.mamba_state, m.mamba_groups,
            m.mamba_chunk) == (64, 64, 128, 8, 128)
    assert (m.router_scale, m.router_renorm_eps, m.norm_eps) == (2.5, 1e-20, 1e-5)
    assert m.vocab_size == CUT["vocab_size"] == 65_536


def test_state_and_kv_a_token_are_the_programs():
    m = MODEL_CONFIGS[CUT["engine_key"]]
    ecfg = EngineConfig(**CUT["engine"])
    assert sm.state_bytes_per_sequence(CUT) == state_bytes_per_slot(m, ecfg)
    assert sm.state_bytes_per_sequence(CUT) == 6 * (128 * 4096 + 3 * 6144) * 2
    # two attention blocks x K and V x 2 heads x 128, bf16: a page row of 256
    assert sm.kv_bytes_per_token(CUT) == 2 * 2 * 256 * 2 == 2048


def test_a_decode_step_is_the_issues_eleven_gigabytes():
    got = sm.decode_bytes_per_step(
        CUT, batch=256, mean_ctx=900, state_rows=256, experts_touched=64)
    assert 10.8e9 < got < 11.1e9
    held = 6 * 64 * sm.expert_params(sm.dims(CUT)) * 2
    assert 0.69 < held / got < 0.72          # the held experts: 7.66 GB
    fewer = sm.decode_bytes_per_step(
        CUT, batch=256, mean_ctx=900, state_rows=256, experts_touched=32)
    assert got - fewer == pytest.approx(6 * 32 * 9_977_856 * 2)


ATTRS = {"steps": 8, "batch": 250, "avg_ctx": 800, "state_rows": 250,
         "state_bytes": 250 * 6_512_640, "experts_touched": 63.5,
         "expert_rows_max": 30.0, "expert_rows_mean": 11.7,
         "experts_held": 64, "expert_rows_held": 36_000,
         "expert_rows_elsewhere": 37_728}


def test_the_roofline_reads_the_spans_and_the_counts():
    got = ssm_moe_decode_hbm_roofline.read(reading(CUT, [ATTRS, ATTRS]))
    want = sm.decode_bytes_per_step(
        CUT, batch=250, mean_ctx=800, state_rows=250, experts_touched=63.5)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.02)
    assert 60.0 < got < 70.0
    # a program whose spans lack either count, and a configuration of
    # another family, read nothing; neither raises
    for gone in ("state_rows", "experts_touched"):
        bare = {k: v for k, v in ATTRS.items() if k != gone}
        assert ssm_moe_decode_hbm_roofline.read(reading(CUT, [bare])) is None
    granite = json.loads(
        (PERFBENCH / "configs/granite-4.0-h-micro-v5e1.json").read_text())
    assert ssm_moe_decode_hbm_roofline.read(reading(granite, [ATTRS])) is None
    assert grouped_matmul_decode_hbm_roofline.read(
        reading(granite, [ATTRS])) is None


def test_the_held_share_reads_what_landed_here_over_what_was_routed():
    r = reading(CUT, [ATTRS, dict(ATTRS, expert_rows_held=38_000,
                                  expert_rows_elsewhere=35_728)])
    assert moe_rows_held_share.read(r) == pytest.approx(74_000 / 147_456)
    bare = {k: v for k, v in ATTRS.items() if "rows_" not in k}
    assert moe_rows_held_share.read(reading(CUT, [bare])) is None
    assert moe_rows_held_share.read(reading(CUT, [])) is None


def test_the_grouped_products_roofline_reads_the_ops_and_the_spans():
    r = reading(CUT, [ATTRS, ATTRS])
    r.spans.append(("prefill", 3.0, 3.1, {"experts_touched": 60.0}))
    r.trace["module_s"]["jit__prefill_jit"] = {"s": 0.06, "runs": 3.0}
    # no grouped_matmul op in the trace (the products on ragged_dot)
    r.trace["op_s"] = {"ragged-dot-none": 1.0}
    assert grouped_matmul_decode_hbm_roofline.read(r) is None
    r.trace["op_s"] = {"grouped_matmul": 0.2, "fusion": 1.0}
    # two runs of the decode program x 8 steps, three prefills: counted
    # as decode_step_device_ms counts them, not by the spans' number
    touched = 2 * 8 * 63.5 + 3 * 60.0
    want = touched * 6 * 9_977_856 * 2 / 819e9
    assert grouped_matmul_decode_hbm_roofline.read(r) == pytest.approx(
        100.0 * want / 0.2)
    assert grouped_matmul_decode_hbm_roofline.read(r) < 100.0
    r.spans.extend(r.spans[:2])       # a span twice (dispatch and fetch)
    assert grouped_matmul_decode_hbm_roofline.read(r) == pytest.approx(
        100.0 * want / 0.2)


def test_the_cell_is_listed_where_its_readers_find_something():
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "engine_host_us_per_row", "decode_step_device_ms",
        "prefill_device_us_per_token", "decode_row_steps_kept_share",
        "moe_expert_rows_max_over_mean", "state_fallback_prefill_share",
        "state_slot_occupancy", "ssm_moe_decode_hbm_roofline",
        "moe_rows_held_share", "grouped_matmul_decode_hbm_roofline",
    }
    for m in BENCH["per_layer"]:
        if m["name"] in ("ssm_moe_decode_hbm_roofline", "moe_rows_held_share",
                         "grouped_matmul_decode_hbm_roofline"):
            assert CELL in m["workloads"]
            assert m["moves"] == "out_tokens_per_s_per_chip"
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "out_tokens_per_s_per_chip")
    assert CELL in e2e["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "generate-long-output-jobs"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
