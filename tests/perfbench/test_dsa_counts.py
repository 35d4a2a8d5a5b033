"""``bytes_and_flops_dsa.py`` against the weights the program builds and
against ISSUE 46's inventory, and the three readers that a model of
latent-attention layers under an indexer's selection brings, on
hand-made readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops_dsa as dsa
from perfbench import bytes_and_flops_mla as mla
from perfbench.layer_metrics import (
    dsa_moe_decode_hbm_roofline, dsa_prefill_mxu_roofline,
    sparse_kv_rows_read_share,
)
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(dsa.__file__).parent
CUT = json.loads(
    (PERFBENCH / "configs/glm-5-l5-ep16-v5e1.json").read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-glm-dsa-cpu.json").read_text())
BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELL = "glm-5-l5-ep16.generate-long-doc-jobs"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert dsa.param_count(cfg) == served(cfg["engine_key"]) == cfg["parameters"]


def test_the_cut_is_the_issues_inventory():
    d = dsa.dims(CUT)
    assert (d["L"], d["dense_layers"], d["moe_layers"]) == (5, 1, 4)
    assert (d["E_held"], d["E_router"], d["top_k"]) == (16, 256, 8)
    assert (d["NHi"], d["Di"], d["topk"]) == (32, 128, 2048)
    assert mla.mla_params(d) == 165_022_208 == (
        6144 * 2048 + 2048 + 2048 * 16384 + 6144 * 576 + 512 + 512 * 28672
        + 16384 * 6144
    )
    assert dsa.indexer_params(d) == 9_371_904 == (
        2048 * 4096 + 6144 * 128 + 256 + 6144 * 32)
    assert mla.expert_params(d) == 37_748_736
    dense = mla.dense_layer_params(d) + dsa.indexer_params(d)
    routed = mla.routed_layer_params(d) + dsa.indexer_params(d)
    assert (dense, routed) == (400_898_816, 817_708_032)
    assert dsa.param_count(CUT) == (
        400_898_816 + 4 * 817_708_032 + 237_895_680 + 6_144
    ) == 3_909_632_768
    assert 7.81e9 < 2 * dsa.param_count(CUT) < 7.83e9
    # the whole published model: 3 dense and 75 routed layers of 256
    # experts, the whole vocabulary, without its multi-token-prediction
    # block: the catalog's 744B
    pub = dict(CUT, **{k: CUT["published"][k] for k in CUT["reduced"]})
    pub["share"] = dict(CUT["share"], experts_published=256)
    assert dsa.param_count(pub) == CUT["published"]["parameters"] == served(
        "glm-5") == 743_911_218_432
    # a routed layer whole is 19.8 GB: none fits a chip
    whole = mla.routed_layer_params(d, 256) + dsa.indexer_params(d)
    assert whole == 9_877_404_672


def test_the_file_states_the_cut_and_changes_no_width():
    assert CUT["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert CUT["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154_880,
        "parameters": 743_911_218_432,
    }
    assert CUT["share"] == {"chips_per_layer": 16, "rank": 0,
                            "experts_published": 256, "first_expert": 0,
                            "vocab_rows": "0-19359"}
    assert CUT["num_experts"] == 256 and CUT["n_routed_experts"] == 16
    row = next(
        json.loads(line) for line in CATALOG.open() if '"GLM-5"' in line
    ) if CATALOG.exists() else None
    if row is not None:
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
        assert CUT["source"] == row["source_url"]
    for key in ("mtp", "hadamard", "index_keys", "index_norm_eps", "weights",
                "tokenizer", "context", "decode_batch_size", "attention",
                "kv_pool"):
        assert CUT["assumed"][key], key
    entry = next(c for c in BENCH["configs"] if c["name"] == CUT["name"])
    assert entry["reduced"] == CUT["reduced"] and entry["source"] == CUT["source"]
    assert entry["file"] == "perfbench/configs/glm-5-l5-ep16-v5e1.json"
    # the preset is the file's model
    m = MODEL_CONFIGS[CUT["engine_key"]]
    assert (m.hidden_size, m.intermediate_size, m.moe_intermediate_size,
            m.moe_shared_intermediate_size) == (6144, 12288, 2048, 2048)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (2048, 512, 192, 64, 256)
    assert (m.index_n_heads, m.index_head_dim, m.index_topk) == (32, 128, 2048)
    assert (m.moe_experts, m.experts_held, m.moe_first_expert, m.moe_top_k) == (
        256, 16, 0, 8)
    assert (m.router_scale, m.router_renorm_eps, m.norm_eps, m.rope_theta) == (
        2.5, 1e-20, 1e-5, 1_000_000.0)
    assert m.rope_interleave and m.num_dense_layers == 1
    assert (m.num_layers, m.vocab_size, m.num_heads) == (5, 19_360, 64)
    assert (m.head_dim, m.num_kv_heads) == (CUT["head_dim"],
                                            CUT["num_key_value_heads"])
    assert m.latent_width == 576 and m.pool_row_widths == (640, 128)
    eng = CUT["engine"]
    assert (eng["decode_batch_size"], eng["kv_page_size"],
            eng["max_pages_per_seq"], eng["max_model_len"],
            eng["prefill_chunk"], eng["prefill_batch_size"],
            eng["interactive_slots"], eng["max_batch_tokens"],
            eng["max_new_tokens"]) == (
        16, 64, 256, 16384, 16384, 1, 0, 262_144, 320)


def test_a_token_keeps_a_latent_row_and_an_index_key_a_layer():
    assert dsa.cache_bytes_per_token(CUT) == 5 * (1152 + 256) == 7_040
    m = MODEL_CONFIGS[CUT["engine_key"]]
    # counted at the widths in use (576 + 128), not at the pools' lanes
    # (640 + 128 = 7,680 bytes a token, 491,520 a page)
    assert dsa.cache_bytes_per_token(CUT) == m.num_latent_layers * (
        m.latent_width + m.index_key_width) * 2
    assert dsa.cache_bytes_per_token(TINY, 4) == 4 * (44 + 24) * 4


def test_a_decode_step_by_hand():
    # 16 rows of mean context 7,450, 39 % of the held experts touched
    got = dsa.decode_bytes_per_step(
        CUT, batch=16, mean_ctx=7450, experts_touched=0.39 * 16)
    keys = 16 * 7450 * 5 * 256
    rows = 16 * 2048 * 5 * 1152
    assert 0.15e9 < keys < 0.16e9 and 0.18e9 < rows < 0.19e9
    weights = 2 * (
        400_898_816 + 4 * (817_708_032 - (16 - 6.24) * 37_748_736)
        + 6_144 + 6144 * 19_360
    )
    assert 4.6e9 < weights < 4.7e9
    assert got == pytest.approx(weights + keys + rows + 16 * 7_040)
    # dense latent attention would read every row: 0.69 GB
    assert 0.68e9 < 16 * 7450 * 5 * 1152 < 0.69e9
    # under index_topk a row reads what it has
    short = dsa.sparse_read_bytes_per_row(CUT, 1000)
    assert short == 5 * 2 * 1000 * (128 + 576)
    fewer = dsa.decode_bytes_per_step(
        CUT, batch=16, mean_ctx=7450, experts_touched=3.0)
    assert got - fewer == pytest.approx(4 * (6.24 - 3.0) * 37_748_736 * 2)


def test_a_prefilled_row_by_hand():
    d = dsa.dims(CUT)
    per_token = (
        5 * (165_022_208 + 9_371_904) + 3 * 6144 * 12288
        + 4 * (6144 * 256 + 37_748_736 + 8 * 16 / 256 * 37_748_736)
    )
    assert 2.6e9 < 2 * per_token < 2.7e9          # the issue's 2.7 GFLOP a token
    for n in (1500.0, 8192.0, 16384.0):
        causal = n * (n + 1) / 2
        short = min(n, 2048.0)
        kept = short * (short + 1) / 2 + (n - short) * 2048
        want = 2 * (per_token * n + 5 * (32 * 128 * causal + 64 * 512 * kept)
                    + 6144 * 19_360)
        assert dsa.prefill_flops_per_row(CUT, n) == pytest.approx(want)
    assert d["Dn"] + d["Dr"] + d["Dv"] == 512
    # at 16,384 a masked dense product computes about 4 x the pairs the
    # selection needs: what dsa_prefill_mxu_roofline shows as lost share
    n = 16384.0
    kept = 2048 * 2049 / 2 + (n - 2048) * 2048
    assert 4.0 < (n * (n + 1) / 2) / kept < 4.5


ATTRS = {"steps": 8, "batch": 16, "avg_ctx": 7450, "experts_touched": 6.2,
         "expert_rows_max": 2.0, "expert_rows_mean": 0.5, "experts_held": 16,
         "expert_rows_held": 256, "expert_rows_elsewhere": 3_840,
         "kv_rows_context": 7450, "kv_rows_selected": 2048}


def test_the_decode_roofline_reads_the_spans_and_the_counts():
    got = dsa_moe_decode_hbm_roofline.read(reading(CUT, [ATTRS, ATTRS], 0.012))
    want = dsa.decode_bytes_per_step(
        CUT, batch=16, mean_ctx=7450, experts_touched=6.2)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.012)
    assert 45.0 < got < 55.0
    bare = {k: v for k, v in ATTRS.items() if k != "experts_touched"}
    assert dsa_moe_decode_hbm_roofline.read(reading(CUT, [bare])) is None
    assert dsa_moe_decode_hbm_roofline.read(reading(CUT, [])) is None
    # a configuration with no indexer (latent attention alone, a dense
    # model) and an untraced run read nothing; none raises
    for other in ("joyai-llm-flash-ep16-v5e1", "qwen3-4b-v5e1"):
        cfg = json.loads((PERFBENCH / f"configs/{other}.json").read_text())
        assert dsa_moe_decode_hbm_roofline.read(reading(cfg, [ATTRS])) is None
    untraced = reading(CUT, [ATTRS])
    untraced.trace = None
    assert dsa_moe_decode_hbm_roofline.read(untraced) is None


def test_the_prefill_roofline_reads_each_rows_own_length():
    r = reading(CUT, [ATTRS])
    assert dsa_prefill_mxu_roofline.read(r) is None       # no prefill program
    r.trace["module_s"]["jit__prefill_jit"] = {"s": 3.0, "runs": 3.0}
    assert dsa_prefill_mxu_roofline.read(r) is None       # no prefill span
    rows = [5100.0, 7900.0, 15500.0]
    r.spans.extend(
        ("prefill", 3.0 + i, 3.1 + i, {"tokens": n}) for i, n in enumerate(rows))
    r.spans.append(("prefill", 9.0, 9.1, {}))             # a span without tokens
    want = sum(dsa.prefill_flops_per_row(CUT, n) for n in rows) / 197e12
    assert dsa_prefill_mxu_roofline.read(r) == pytest.approx(100.0 * want / 3.0)
    assert 0.0 < dsa_prefill_mxu_roofline.read(r) < 100.0
    joyai = json.loads(
        (PERFBENCH / "configs/joyai-llm-flash-ep16-v5e1.json").read_text())
    other = reading(joyai, [ATTRS])
    other.trace["module_s"]["jit__prefill_jit"] = {"s": 0.4, "runs": 3.0}
    assert dsa_prefill_mxu_roofline.read(other) is None
    untraced = reading(CUT, [ATTRS])
    untraced.trace = None
    assert dsa_prefill_mxu_roofline.read(untraced) is None


def test_the_rows_read_share_reads_the_counters_increments():
    name = sparse_kv_rows_read_share.ROWS
    assert sparse_kv_rows_read_share.read(reading(CUT, [])) is None
    reg = ({name: {"series": {"context": 1000.0, "selected": 900.0}}},
           {name: {"series": {"context": 75_500.0, "selected": 21_380.0}}})
    got = sparse_kv_rows_read_share.read(reading(CUT, [], registry=reg))
    assert got == pytest.approx(20_480 / 74_500)
    assert 0.2 < got < 0.4
    # a program without the counter (the parent, any other model)
    still = ({name: {"series": {}}}, {name: {"series": {}}})
    assert sparse_kv_rows_read_share.read(
        reading(CUT, [], registry=still)) is None


def test_the_cell_is_listed_where_its_readers_find_something():
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "engine_host_us_per_row", "decode_step_device_ms",
        "prefill_device_us_per_token", "decode_row_steps_kept_share",
        "moe_expert_rows_max_over_mean",
        "dsa_moe_decode_hbm_roofline", "dsa_prefill_mxu_roofline",
        "sparse_kv_rows_read_share",
    }
    for m in BENCH["per_layer"]:
        if m["name"].startswith("dsa_") or m["name"].startswith("sparse_"):
            assert CELL in m["workloads"]
            assert m["moves"] == "out_tokens_per_s_per_chip"
        if m["name"].startswith("mla_"):
            assert CELL not in m["workloads"]
    for module in (dsa_moe_decode_hbm_roofline, dsa_prefill_mxu_roofline):
        assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE,
                module.MOVES) == ("kernels", "%", "higher", "device_trace",
                                  "out_tokens_per_s_per_chip")
    s = sparse_kv_rows_read_share
    assert (s.LAYER, s.UNIT, s.BETTER, s.SOURCE, s.MOVES) == (
        "runner and model", "ratio", "lower", "program_counter",
        "out_tokens_per_s_per_chip")
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "out_tokens_per_s_per_chip")
    # (nothing here counts the benchmark's cells or configurations, or
    # asks to be the last: a later PR appends its own)
    assert CELL in e2e["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "generate-long-doc-jobs"
    assert cell["config"] == CUT["name"]
