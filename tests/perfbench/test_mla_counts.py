"""``bytes_and_flops_mla.py`` against the weights the program builds and
against ISSUE 42's inventory, and the two readers that a model of latent
attention layers with a held share of experts brings, on hand-made
readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops_mla as mla
from perfbench.layer_metrics import (
    mla_moe_decode_hbm_roofline, mla_paged_decode_hbm_roofline,
    mla_prefill_mxu_roofline,
)
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(mla.__file__).parent
CUT = json.loads(
    (PERFBENCH / "configs/joyai-llm-flash-ep16-v5e1.json").read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-joyai-cpu.json").read_text())
BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELL = "joyai-llm-flash-ep16.generate-long-prompt-jobs"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert mla.param_count(cfg) == served(cfg["engine_key"]) == cfg["parameters"]


def test_the_cut_is_the_issues_inventory():
    d = mla.dims(CUT)
    assert (d["L"], d["dense_layers"], d["moe_layers"]) == (40, 1, 39)
    assert (d["E_held"], d["E_router"], d["top_k"]) == (16, 256, 8)
    assert mla.mla_params(d) == 26_347_520 == (
        2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512 + 512 * 8192
        + 4096 * 2048
    )
    assert mla.expert_params(d) == 4_718_592
    assert mla.dense_layer_params(d) == 70_391_808
    assert mla.routed_layer_params(d) == 107_092_224
    assert mla.routed_layer_params(d, 256) == 1_239_554_304
    assert mla.param_count(CUT) == (
        70_391_808 + 39 * 107_092_224 + 529_530_880 + 2_048
    ) == 4_776_521_472
    assert 9.54e9 < 2 * mla.param_count(CUT) < 9.56e9
    # the whole published model, every expert, without its
    # multi-token-prediction block: the catalog's 48B
    pub = dict(CUT, **{k: CUT["published"][k] for k in CUT["reduced"]})
    assert mla.param_count(pub) == CUT["published"]["parameters"] == served(
        "joyai-llm-flash") == 48_942_542_592


def test_the_file_states_the_cut_and_changes_no_width():
    assert CUT["reduced"] == ["n_routed_experts"]
    assert CUT["published"]["n_routed_experts"] == 256
    assert CUT["share"] == {"chips_per_layer": 16, "rank": 0,
                            "experts_published": 256, "first_expert": 0}
    assert CUT["num_experts"] == 256 and CUT["n_routed_experts"] == 16
    row = next(
        json.loads(line) for line in CATALOG.open()
        if '"JoyAI-LLM-Flash"' in line
    ) if CATALOG.exists() else None
    if row is not None:
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
        assert CUT["source"] == row["source_url"]
    for key in ("mtp", "ep_size", "weights", "tokenizer", "context",
                "decode_batch_size", "attention", "kv_pool"):
        assert CUT["assumed"][key], key
    entry = next(c for c in BENCH["configs"] if c["name"] == CUT["name"])
    assert entry["reduced"] == CUT["reduced"] and entry["source"] == CUT["source"]
    assert entry["file"] == "perfbench/configs/joyai-llm-flash-ep16-v5e1.json"
    # the preset is the file's model
    m = MODEL_CONFIGS[CUT["engine_key"]]
    assert (m.hidden_size, m.intermediate_size, m.moe_intermediate_size,
            m.moe_shared_intermediate_size) == (2048, 7168, 768, 768)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (m.moe_experts, m.experts_held, m.moe_first_expert, m.moe_top_k) == (
        256, 16, 0, 8)
    assert (m.router_scale, m.router_renorm_eps, m.norm_eps, m.rope_theta) == (
        2.5, 1e-20, 1e-6, 32_000_000.0)
    assert m.rope_interleave and m.num_dense_layers == 1
    assert (m.num_layers, m.vocab_size) == (40, 129_280)
    # the benchmark's own test reads head_dim and num_kv_heads from the
    # file; neither sizes the cache
    assert (m.head_dim, m.num_kv_heads) == (CUT["head_dim"],
                                            CUT["num_key_value_heads"])
    assert m.latent_width == 576 and m.page_width == 640
    eng = CUT["engine"]
    assert (eng["decode_batch_size"], eng["kv_page_size"],
            eng["max_pages_per_seq"], eng["max_model_len"],
            eng["prefill_chunk"], eng["prefill_batch_size"],
            eng["interactive_slots"], eng["max_batch_tokens"]) == (
        32, 64, 64, 4096, 4096, 1, 0, 131_072)


def test_a_token_keeps_one_latent_row_a_layer():
    assert mla.latent_bytes_per_token(CUT) == 40 * 1152 == 46_080
    # where 32 heads of K (192) and V (128) would be 20,480 a layer
    assert 32 * (192 + 128) * 2 == 20_480
    m = MODEL_CONFIGS[CUT["engine_key"]]
    # counted at the row in use, not at the pool's padded row
    assert mla.latent_bytes_per_token(CUT) == m.num_latent_layers * m.latent_width * 2
    assert mla.latent_bytes_per_token(TINY, 4) == 4 * 48 * 4


def test_a_decode_step_is_the_issues_nine_gigabytes():
    # 32 rows of mean context 1,750, 63 % of the held experts touched
    got = mla.decode_bytes_per_step(
        CUT, batch=32, mean_ctx=1750, experts_touched=0.63 * 16)
    assert 9.3e9 < got < 9.5e9
    latents = 32 * 46_080 * 1751
    assert 2.5e9 < latents < 2.7e9
    d = mla.dims(CUT)
    projections = 40 * mla.mla_params(d) * 2
    assert 2.0e9 < projections < 2.2e9
    # latent attention (its projections and the cached rows) is half
    assert 0.48 < (latents + projections) / got < 0.52
    fewer = mla.decode_bytes_per_step(
        CUT, batch=32, mean_ctx=1750, experts_touched=5.0)
    assert got - fewer == pytest.approx(39 * (0.63 * 16 - 5.0) * 4_718_592 * 2)


def test_a_prefilled_row_by_hand():
    d = mla.dims(CUT)
    n = 1680.0
    per_token = (
        40 * 26_347_520 + 3 * 2048 * 7168
        + 39 * (2048 * 256 + 4_718_592 + 8 * 16 / 256 * 4_718_592)
    )
    attn = 40 * 32 * (192 + 128) * n * (n + 1) / 2
    want = 2 * (per_token * n + attn + 2048 * 129_280)
    assert mla.prefill_flops_per_row(CUT, n) == pytest.approx(want)
    assert 3.4e9 < want / n < 3.5e9              # the issue's 3.4 GFLOP a token
    # the projections and the attention are four fifths of it
    mine = 2 * (40 * 26_347_520 * n + attn)
    assert 0.75 < mine / want < 0.85
    assert d["Dn"] + d["Dr"] + d["Dv"] == 320
    # a row of twice the length costs more than twice (the square)
    assert mla.prefill_flops_per_row(CUT, 2 * n) > 2 * want


ATTRS = {"steps": 8, "batch": 32, "avg_ctx": 1750, "experts_touched": 10.1,
         "expert_rows_max": 3.0, "expert_rows_mean": 1.0, "experts_held": 16,
         "expert_rows_held": 640, "expert_rows_elsewhere": 9_344}


def test_the_decode_roofline_reads_the_spans_and_the_counts():
    got = mla_moe_decode_hbm_roofline.read(reading(CUT, [ATTRS, ATTRS], 0.03))
    want = mla.decode_bytes_per_step(
        CUT, batch=32, mean_ctx=1750, experts_touched=10.1)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.03)
    assert 35.0 < got < 42.0
    # a program whose spans count no routing, a configuration of another
    # family and an untraced run read nothing; none raises
    bare = {k: v for k, v in ATTRS.items() if k != "experts_touched"}
    assert mla_moe_decode_hbm_roofline.read(reading(CUT, [bare])) is None
    assert mla_moe_decode_hbm_roofline.read(reading(CUT, [])) is None
    dense = json.loads((PERFBENCH / "configs/qwen3-4b-v5e1.json").read_text())
    assert mla_moe_decode_hbm_roofline.read(reading(dense, [ATTRS])) is None
    untraced = reading(CUT, [ATTRS])
    untraced.trace = None
    assert mla_moe_decode_hbm_roofline.read(untraced) is None


def test_the_prefill_roofline_reads_each_rows_own_length():
    r = reading(CUT, [ATTRS])
    assert mla_prefill_mxu_roofline.read(r) is None       # no prefill program
    r.trace["module_s"]["jit__prefill_jit"] = {"s": 0.4, "runs": 3.0}
    assert mla_prefill_mxu_roofline.read(r) is None       # no prefill span
    rows = [1200.0, 1900.0, 3300.0]
    r.spans.extend(
        ("prefill", 3.0 + i, 3.1 + i, {"tokens": n}) for i, n in enumerate(rows))
    r.spans.append(("prefill", 9.0, 9.1, {}))             # a span without tokens
    want = sum(mla.prefill_flops_per_row(CUT, n) for n in rows) / 197e12
    assert mla_prefill_mxu_roofline.read(r) == pytest.approx(100.0 * want / 0.4)
    assert 20.0 < mla_prefill_mxu_roofline.read(r) < 40.0
    dense = json.loads((PERFBENCH / "configs/qwen3-4b-v5e1.json").read_text())
    other = reading(dense, [ATTRS])
    other.trace["module_s"]["jit__prefill_jit"] = {"s": 0.4, "runs": 3.0}
    assert mla_prefill_mxu_roofline.read(other) is None
    untraced = reading(CUT, [ATTRS])
    untraced.trace = None
    assert mla_prefill_mxu_roofline.read(untraced) is None


def test_the_paged_kernels_roofline_reads_the_rows_latents_and_its_ops():
    r = reading(CUT, [ATTRS, ATTRS], 0.03)
    assert mla_paged_decode_hbm_roofline.read(r) is None   # no such op: XLA
    steps = r.trace["module_s"]
    n_steps = sum(m["runs"] for k, m in steps.items() if "decode" in k) * 8
    r.trace["op_s"] = {"paged_decode_attention": 0.5 * n_steps * 0.03,
                       "fusion": 1.0}
    # 32 rows x 1,750 tokens x 46,080 bytes a step, over the kernel's
    # half of each 30 ms step
    want = 32 * 1750 * 46_080 / 819e9 / (0.5 * 0.03)
    assert mla_paged_decode_hbm_roofline.read(r) == pytest.approx(100.0 * want)
    assert 15.0 < 100.0 * want < 25.0
    bare = {k: v for k, v in ATTRS.items() if k != "avg_ctx"}
    other = reading(CUT, [bare], 0.03)
    other.trace["op_s"] = dict(r.trace["op_s"])
    assert mla_paged_decode_hbm_roofline.read(other) is None
    dense = json.loads((PERFBENCH / "configs/qwen3-4b-v5e1.json").read_text())
    other = reading(dense, [ATTRS], 0.03)
    other.trace["op_s"] = dict(r.trace["op_s"])
    assert mla_paged_decode_hbm_roofline.read(other) is None
    untraced = reading(CUT, [ATTRS])
    untraced.trace = None
    assert mla_paged_decode_hbm_roofline.read(untraced) is None


def test_the_cell_is_listed_where_its_readers_find_something():
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "engine_host_us_per_row", "decode_step_device_ms",
        "prefill_device_us_per_token", "decode_row_steps_kept_share",
        "moe_expert_rows_max_over_mean",
        "mla_moe_decode_hbm_roofline", "mla_prefill_mxu_roofline",
        "mla_paged_decode_hbm_roofline",
    }
    for m in BENCH["per_layer"]:
        if m["name"].startswith("mla_"):
            assert CELL in m["workloads"]
            assert (m["moves"], m["layer"], m["unit"], m["source"]) == (
                "out_tokens_per_s_per_chip", "kernels", "%", "device_trace")
    for module in (mla_moe_decode_hbm_roofline, mla_prefill_mxu_roofline,
                   mla_paged_decode_hbm_roofline):
        assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
                module.MOVES) == ("kernels", "%", "higher", "device_trace",
                                  "out_tokens_per_s_per_chip")
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "out_tokens_per_s_per_chip")
    # (nothing here counts the benchmark's cells or asks to be the last:
    # a later PR appends its own)
    assert CELL in e2e["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "generate-long-prompt-jobs"
    assert cell["config"] == CUT["name"]
    assert len(BENCH["workloads"]) >= 8 and len(BENCH["configs"]) >= 7
