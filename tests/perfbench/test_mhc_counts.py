"""``bytes_and_flops_mhc.py`` against the weights the program builds and
against ISSUE 54's inventory, and the five readers that a model whose
residual stream is several lanes brings, on hand-made readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops_mhc as mh
from perfbench import trace_parts
from perfbench.layer_metrics import (
    mhc_mla_moe_decode_hbm_roofline, mhc_paged_decode_hbm_roofline,
    mhc_prefill_mxu_roofline, mhc_share_of_busy, mhc_stream_hbm_roofline,
    mla_paged_decode_hbm_roofline,
)
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(mh.__file__).parent
CUT = json.loads((
    PERFBENCH / "configs/xing4.0-29b-a4b-l7-v5e1.json"
).read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-xing-mhc-cpu.json").read_text()
)
BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELL = "xing4.0-29b-a4b-l7.generate-long-prompt-jobs"
READERS = (
    mhc_mla_moe_decode_hbm_roofline, mhc_prefill_mxu_roofline,
    mhc_stream_hbm_roofline, mhc_share_of_busy,
    mhc_paged_decode_hbm_roofline,
)
#: what ONE token's stream must move: 14 sublayers read and write 4 lanes
#: of 3,584 once each, bf16
STREAM = 14 * 2 * 4 * 3584 * 2


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert mh.param_count(cfg) == served(cfg["engine_key"]) == cfg["parameters"]


def test_the_cut_and_the_whole_are_the_issues_inventory():
    d = mh.dims(CUT)
    assert (d["L"], d["dense_layers"], d["moe_layers"], d["sublayers"]) == (7, 2, 5, 14)
    assert (d["E_held"], d["E_router"], d["top_k"], d["n"]) == (64, 64, 4, 4)
    assert mh.mla.mla_params(d) == 28_411_136
    assert mh.hc_params(d) == 14_336 * 24 + 24 + 3 == 344_091
    assert mh.mla.expert_params(d) == 11_010_048
    assert mh.mla.dense_layer_params(d) + 2 * mh.hc_params(d) == 128_196_918
    assert mh.mla.routed_layer_params(d) + 2 * mh.hc_params(d) == 744_989_046
    assert mh.param_count(CUT) == (
        2 * 128_196_918 + 5 * 744_989_046 + 939_524_096 + 3_584
    ) == 4_920_866_746
    assert 9.83e9 < 2 * mh.param_count(CUT) < 9.85e9
    # the whole published model: the catalog's 29B-A4B
    whole = dict(CUT, num_hidden_layers=CUT["published"]["num_hidden_layers"])
    assert mh.param_count(whole) == CUT["published"]["parameters"] == (
        served("xing4.0-29b-a4b")) == 29_505_505_264
    assert mh.active_params_per_token(whole) == 3_932_833_776


def test_the_file_states_the_cut_and_changes_no_width():
    assert CUT["reduced"] == ["num_hidden_layers"]
    assert CUT["published"]["num_hidden_layers"] == 40
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.open()
                   if '"Xing4.0-29B-A4B"' in line)
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
        assert CUT["source"] == row["source_url"]
    for key in ("hyper_connections", "stream", "sinkhorn", "precision",
                "rope", "router_renorm_eps", "mtp", "weights", "context",
                "kv_pool", "tokenizer", "prefix_store"):
        assert CUT["assumed"][key], key
    assert CUT["deployment"] and "share" not in CUT
    entry = next(c for c in BENCH["configs"] if c["name"] == CUT["name"])
    assert entry["reduced"] == CUT["reduced"] and entry["source"] == CUT["source"]
    m = MODEL_CONFIGS[CUT["engine_key"]]
    assert (m.hidden_size, m.intermediate_size, m.moe_intermediate_size,
            m.moe_shared_intermediate_size) == (3584, 9216, 1024, 1024)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (768, 512, 128, 64, 128)
    assert (m.hc_mult, m.hc_sinkhorn_iters, m.hc_eps, m.hc_res_clamp) == (
        CUT["hc_mult"], CUT["hc_sinkhorn_iters"], CUT["hc_eps"],
        CUT["mhc_h_res_clamp_max"]) == (4, 20, 1e-6, 30)
    assert -CUT["mhc_h_res_clamp_min"] == m.hc_res_clamp
    rs = CUT["rope_scaling"]
    assert (m.rope_scaling_factor, m.rope_original_max, m.rope_beta_fast,
            m.rope_beta_slow, m.rope_mscale_all_dim) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"], rs["mscale_all_dim"])
    assert (m.router_scale, m.router_renorm_eps, m.norm_eps, m.rope_theta) == (
        CUT["routed_scaling_factor"], 1e-20, CUT["rms_norm_eps"], CUT["rope_theta"])
    eng = EngineConfig(**CUT["engine"])
    assert eng.decode_batch_size == 128 and eng.prefill_batch_size == 1
    assert eng.max_pages_per_seq * eng.kv_page_size >= eng.max_model_len == 4096


def test_the_stream_and_a_decode_step_are_the_programs():
    # THE definition is the program's: 2 n C elements a sublayer a token
    # (a lane read once and written once), not ISSUE 54's 3 n + 2
    from sutro_tpu.engine.runner import ModelRunner

    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg = MODEL_CONFIGS[CUT["engine_key"]], EngineConfig(**CUT["engine"])
    assert r.stream_bytes(1) == STREAM == 802_816
    got = mh.decode_bytes_per_step(
        CUT, batch=128, mean_ctx=1800, experts_touched=64,
        stream_bytes=r.stream_bytes(128))
    assert 10.7e9 < got < 11.0e9
    plain = mh.mla.decode_bytes_per_step(
        CUT, batch=128, mean_ctx=1800, experts_touched=64)
    assert got - plain == 14 * 344_091 * 2 + 128 * STREAM
    fewer = mh.decode_bytes_per_step(
        CUT, batch=128, mean_ctx=1800, experts_touched=32,
        stream_bytes=r.stream_bytes(128))
    assert got - fewer == pytest.approx(5 * 32 * 11_010_048 * 2)
    # a prefilled token: 1.35 GFLOP in its projections, plus its attention
    per_token = (mh.prefill_flops_per_row(CUT, 1) - 2 * 3584 * 131_072) / 1e9
    assert 1.3 < per_token < 1.4
    # the projection, the read and the mix: (24 + 1 + 5) x 14,336 a sublayer
    assert mh.hc_flops_per_token(CUT) == 2 * 14 * 30 * 14_336


ATTRS = {"steps": 8, "batch": 120, "avg_ctx": 1900, "experts_touched": 63.5,
         "expert_rows_max": 17.0, "expert_rows_mean": 7.5,
         "hc_stream_bytes": 120 * 8 * STREAM}


def test_the_decode_roofline_reads_the_spans_and_the_counts():
    got = mhc_mla_moe_decode_hbm_roofline.read(
        reading(CUT, [ATTRS, ATTRS], step_s=0.025))
    want = mh.decode_bytes_per_step(
        CUT, batch=120, mean_ctx=1900, experts_touched=63.5,
        stream_bytes=120 * STREAM)            # a step's, of the window's 8
    assert got == pytest.approx(100.0 * want / 819e9 / 0.025)
    assert 45.0 < got < 60.0
    for gone in ("experts_touched", "hc_stream_bytes"):
        bare = {k: v for k, v in ATTRS.items() if k != gone}
        assert mhc_mla_moe_decode_hbm_roofline.read(
            reading(CUT, [bare])) is None


def test_the_kernels_own_share_is_the_latent_readers_for_this_family():
    r = reading(CUT, [ATTRS, ATTRS], step_s=0.02)
    assert mhc_paged_decode_hbm_roofline.read(r) is None      # no such op
    r.trace["op_s"] = {"paged_decode_attention.3": 0.06, "fusion.7": 1.0}
    # 16 steps x 120 rows x 1,900 tokens x 7 layers x 576 values, bf16
    want = 16 * 120 * 1900 * 7 * 576 * 2 / 819e9
    got = mhc_paged_decode_hbm_roofline.read(r)
    assert got == pytest.approx(100.0 * want / 0.06)
    assert got == mla_paged_decode_hbm_roofline.read(r) and got < 100.0


def test_the_prefill_share_reads_the_rows_own_lengths():
    r = reading(CUT, [ATTRS])
    assert mhc_prefill_mxu_roofline.read(r) is None        # no prefill ran
    r.spans.append(("prefill", 3.0, 3.1, {"tokens": 1700}))
    r.spans.append(("prefill", 3.2, 3.3, {"tokens": 0, "wave": 1}))
    r.trace["module_s"]["jit__prefill_jit"] = {"s": 0.05, "runs": 1.0}
    want = mh.prefill_flops_per_row(CUT, 1700) / 197e12
    assert mhc_prefill_mxu_roofline.read(r) == pytest.approx(100.0 * want / 0.05)
    assert mhc_prefill_mxu_roofline.read(r) < 100.0


def test_an_op_counts_whole_and_says_whether_another_scope_rides_in_it(
    monkeypatch,
):
    p = "jit(f)/while/body/closed_call/"
    made = {
        # what the barriers end: a mix fused with the product before it
        "fusion.1": ("", [p + "mixer/hc_write/mul"] * 6 + [
            p + "mixer/mla_mixer/dot_general", "", p + "mixer"], []),
        # a write fused with the next sublayer's statistic; a bare name
        "fusion.2": ("", [p + "mixer/hc_write/mul", p + "ffn/hc_coeff/mul",
                          p + "ffn/hc_coeff/reduce_sum", ""], []),
        "fusion.3": ("", [p + "ffn/moe_ffn/dot_general"] * 3, []),
        "copy.4": (p + "mixer/hc_read/convert_element_type", [], []),
        "copy.5": (p + "mixer/mla_mixer/mla_yarn/cos", [], []),
    }
    monkeypatch.setattr(trace_parts, "instructions", lambda proto: made)
    got = mhc_share_of_busy.hc_ops({"jit__prefill_jit(7)": b"-"})
    assert got == {"jit__prefill_jit(7)": {
        "fusion.1": "shared", "fusion.2": "own", "copy.4": "own"}}
    # the seconds: an op's own time whole, by kind
    rows = [("k", "fusion.1", "shared", 0.25), ("k", "fusion.2", "own", 0.5),
            ("k", "fusion.3", "", 2.0), ("k", "copy.4", "own", 0.125)]
    monkeypatch.setattr(trace_parts, "parsed", lambda path: ({}, {}))
    monkeypatch.setattr(trace_parts, "hlo_protos", lambda path: {})
    monkeypatch.setattr(trace_parts, "op_rows", lambda *a: rows)
    assert mhc_share_of_busy.seconds_by_kind("a-trace", (0.0, 1.0)) == {
        "own": 0.625, "shared": 0.25}


def test_the_streams_share_and_roofline_read_the_seconds_under_an_hc_scope(
    monkeypatch,
):
    monkeypatch.setattr(mhc_share_of_busy, "hc_seconds", lambda r: 0.30)
    monkeypatch.setattr(mhc_stream_hbm_roofline, "hc_seconds", lambda r: 0.30)
    r = reading(CUT, [ATTRS])
    r.trace["busy_s"] = 4.0
    r.spans.append(("prefill", 3.0, 3.1, {
        "tokens": 170_000, "hc_stream_bytes": 170_000 * STREAM}))
    assert mhc_share_of_busy.read(r) == pytest.approx(7.5)
    want = (170_000 + 120 * 8) * STREAM / 819e9
    assert mhc_stream_hbm_roofline.read(r) == pytest.approx(100.0 * want / 0.30)
    assert mhc_stream_hbm_roofline.read(r) < 100.0
    # a program without the scopes: no second under one
    for mod in (mhc_share_of_busy, mhc_stream_hbm_roofline):
        monkeypatch.setattr(mod, "hc_seconds", lambda r: 0.0)
        assert mod.read(r) is None


def test_a_program_or_a_configuration_without_the_lanes_reads_nothing():
    """The parent's program (no scope, no span attr) and another family's
    configuration (no ``hc_mult``): every reader returns None, none
    raises; nor on an untraced run."""
    joyai = json.loads((
        PERFBENCH / "configs/joyai-llm-flash-ep16-v5e1.json"
    ).read_text())
    bare = {"steps": 8, "batch": 120, "avg_ctx": 1900}
    for cfg, attrs in ((CUT, bare), (joyai, ATTRS)):
        r = reading(cfg, [attrs])
        r.trace["busy_s"] = 1.0
        for mod in READERS:
            assert mod.read(r) is None, (mod.__name__, cfg["name"])
    r = reading(CUT, [ATTRS])
    r.trace = None
    for mod in READERS:
        assert mod.read(r) is None, mod.__name__


def test_the_cells_readers_list_it_and_no_other():
    names = {mod.__name__.rsplit(".", 1)[1] for mod in READERS}
    for m in BENCH["per_layer"]:
        if m["name"] in names:
            assert CELL in m["workloads"]
            assert m["moves"] == "out_tokens_per_s_per_chip"
        if m["name"].startswith("mla_"):
            assert CELL not in m["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= names | {
        "engine_host_us_per_row", "decode_step_device_ms",
        "prefill_device_us_per_token", "decode_row_steps_kept_share",
        "moe_expert_rows_max_over_mean",
    }
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "out_tokens_per_s_per_chip")
    assert CELL in e2e["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "generate-long-prompt-jobs"
    assert cell["config"] == CUT["name"]
