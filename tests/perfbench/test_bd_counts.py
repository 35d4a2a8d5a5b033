"""``bytes_and_flops_bd.py`` against the weights the program builds and
against ISSUE 57's inventory, the configuration file's keys against the
catalog's, and the five readers that a model that generates by blocks
brings, on hand-made readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops_bd as bd
from perfbench.layer_metrics import (
    bd_forwards_per_token, bd_moe_decode_hbm_roofline,
    bd_paged_decode_hbm_roofline, bd_prefill_mxu_roofline,
    bd_sample_share_of_step,
)
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(bd.__file__).parent
CUT = json.loads((
    PERFBENCH / "configs/sdar-30b-a3b-chat-l6-v5e1.json"
).read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-sdar-cpu.json").read_text()
)
BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELL = "sdar-30b-a3b-chat-l6.generate-block-diffusion-jobs"
READERS = (
    bd_moe_decode_hbm_roofline, bd_paged_decode_hbm_roofline,
    bd_prefill_mxu_roofline, bd_forwards_per_token, bd_sample_share_of_step,
)


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


def test_the_counts_are_the_weights_the_runner_holds():
    assert bd.param_count(CUT) == served(CUT["engine_key"]) == CUT["parameters"]
    assert bd.param_count(TINY) == served("tiny-sdar")


def test_the_cut_and_the_whole_are_the_issues_inventory():
    d = bd.dims(CUT)
    assert (d["L"], d["E"], d["top_k"], d["Bk"]) == (6, 128, 8, 4)
    assert bd.attention_params(d) == 18_874_368 + 256 + 2_048
    assert bd.layer_params(CUT) == 623_120_640
    assert bd.param_count(CUT) == 6 * 623_120_640 + 622_329_856 + 2_048 == (
        4_361_055_744)
    assert 8.72e9 < 2 * bd.param_count(CUT) < 8.73e9
    whole = dict(CUT, num_hidden_layers=CUT["published"]["num_hidden_layers"])
    assert bd.param_count(whole) == CUT["published"]["parameters"] == (
        served("sdar-30b-a3b-chat")) == 30_532_122_624
    # the published "A3B": 56,889,600 a layer a token (attention
    # 18,874,368, norms 4,352, router 262,144, 8 experts 37,748,736: the
    # sum of ISSUE 57's own terms, which it wrote 768 over), 3.04 B with
    # the head
    assert (bd.active_param_count(whole) - 622_329_856 // 2 - 2_048) // 48 == (
        18_874_368 + 4_352 + 262_144 + 8 * 3 * 2048 * 768) == 56_889_600
    assert 3.03e9 < bd.active_param_count(whole) < 3.05e9
    assert bd.kv_bytes_per_token(CUT) == 6 * 2 * 512 * 2 == 12_288


def test_the_file_states_the_cut_and_changes_no_width():
    assert CUT["reduced"] == ["num_hidden_layers"]
    assert CUT["published"]["num_hidden_layers"] == 48
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.open()
                   if '"SDAR-30B-A3B-Chat"' in line)
        for key, value in row["config"].items():
            if key not in CUT["reduced"]:
                assert CUT[key] == value, key
        assert CUT["source"] == row["source_url"]
    for key in ("block_length", "mask_token_id", "generation_defaults",
                "logits", "prompt_tail", "confidence", "mask_never_drawn",
                "qk_norm", "max_window_layers", "weights", "context",
                "tokenizer", "refused"):
        assert CUT["assumed"][key], key
    assert CUT["deployment"] and CUT["reference"] == "sdar_moe"
    assert CUT["kernels"] == [
        "paged_decode", "flash_prefill", "kv_write", "grouped_matmul"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CUT["name"])
    assert entry["reduced"] == CUT["reduced"] and entry["source"] == CUT["source"]
    m = MODEL_CONFIGS[CUT["engine_key"]]
    assert (m.hidden_size, m.intermediate_size, m.moe_intermediate_size,
            m.num_heads, m.num_kv_heads, m.head_dim) == (
        2048, 6144, 768, 32, 4, 128)
    assert (m.block_length, m.mask_token_id, m.denoising_steps, m.remasking,
            m.confidence_threshold) == (
        CUT["block_length"], CUT["mask_token_id"], 0,
        "low_confidence_dynamic", 0.9)
    assert (m.norm_eps, m.rope_theta, m.qk_norm, m.seeded_unit_embedding) == (
        CUT["rms_norm_eps"], CUT["rope_theta"], True, True)
    eng = EngineConfig(**CUT["engine"])
    assert eng.decode_batch_size == 128 and eng.prefill_batch_size == 1
    assert eng.max_pages_per_seq * eng.kv_page_size >= eng.max_model_len == 2048
    assert eng.kv_page_size % m.block_length == 0
    t = json.loads((PERFBENCH / "traffic/generate-block-diffusion-jobs.json"
                    ).read_text())
    base = json.loads((PERFBENCH / "traffic/generate-long-output-jobs.json"
                       ).read_text())
    assert t["sampling"] == {"temperature": 0.7, "denoising_steps": 2,
                             "remasking": "low_confidence_static"}
    for key in base:
        if key not in ("name", "job_name", "why", "sampling"):
            assert t[key] == base[key], key


def test_a_forwards_bytes_by_kind():
    common = dict(batch=128, ctx=560, experts_touched=128)
    den = bd.forward_bytes(CUT, kind="denoise", **common)
    com = bd.forward_bytes(CUT, kind="commit", **common)
    # six layers' experts 7.25 GB, attention 0.23, K/V of 128 rows x 560
    # tokens 0.88; a denoising forward the head 0.62 and its logits 0.62
    assert 9.5e9 < den < 9.7e9 and 8.3e9 < com < 8.4e9
    assert den - com == pytest.approx(
        2048 * 151_936 * 2 + 2 * 4 * 128 * 4 * 151_936 - 128 * 4 * 12_288)
    fewer = bd.forward_bytes(CUT, kind="commit", batch=128, ctx=560,
                             experts_touched=64)
    assert com - fewer == pytest.approx(6 * 64 * 3 * 2048 * 768 * 2)
    assert bd.block_kv_bytes(CUT, batch=128, ctx=560) == 128 * 560 * 12_288
    with pytest.raises(ValueError):
        bd.forward_bytes(CUT, kind="verify", **common)
    # a prefilled token: 0.68 GFLOP in its products (the head on one)
    per = (bd.prefill_flops_per_row(CUT, 1) - 2 * 2048 * 151_936) / 1e9
    assert 0.68 < per < 0.70


ATTRS = {"steps": 6, "blocks": 2, "block_length": 4, "denoise_forwards": 4,
         "commit_forwards": 2, "tokens": 8, "batch": 120, "avg_ctx": 600,
         "experts_touched": 90.0, "expert_rows_max": 60.0,
         "expert_rows_mean": 30.0}


def _reading(attrs, forward_s=0.02):
    # ``reading`` books 8 steps of ``step_s`` a run: a window of 6 forwards
    return reading(CUT, attrs, step_s=forward_s * 6 / 8)


def test_the_whole_steps_share_reads_the_forwards_by_kind():
    got = bd_moe_decode_hbm_roofline.read(_reading([ATTRS, ATTRS]))
    kw = dict(batch=120, ctx=600, experts_touched=90.0)
    want = (4 * bd.forward_bytes(CUT, kind="denoise", **kw)
            + 2 * bd.forward_bytes(CUT, kind="commit", **kw)) / 6
    assert got == pytest.approx(100.0 * want / 819e9 / 0.02)
    assert 35.0 < got < 50.0
    for gone in ("experts_touched", "denoise_forwards"):
        bare = {k: v for k, v in ATTRS.items() if k != gone}
        assert bd_moe_decode_hbm_roofline.read(_reading([bare])) is None


def test_the_block_kernels_own_share():
    r = _reading([ATTRS, ATTRS])
    assert bd_paged_decode_hbm_roofline.read(r) is None       # no such op
    r.trace["op_s"] = {"paged_decode_attention.3": 0.03, "fusion.7": 1.0}
    # 12 forwards x 120 rows x 600 tokens x 12,288 B
    want = 12 * 120 * 600 * 12_288 / 819e9
    got = bd_paged_decode_hbm_roofline.read(r)
    assert got == pytest.approx(100.0 * want / 0.03) and got < 100.0


def test_the_prefill_share_reads_the_rows_own_lengths():
    r = _reading([ATTRS])
    assert bd_prefill_mxu_roofline.read(r) is None           # no prefill ran
    r.spans.append(("prefill", 3.0, 3.1, {"tokens": 300, "batch": 1}))
    r.spans.append(("prefill", 3.2, 3.3, {"tokens": 0, "wave": 1}))
    r.trace["module_s"]["jit__prefill_jit"] = {"s": 0.011, "runs": 1.0}
    want = bd.prefill_flops_per_row(CUT, 300) / 197e12
    assert bd_prefill_mxu_roofline.read(r) == pytest.approx(100.0 * want / 0.011)
    assert bd_prefill_mxu_roofline.read(r) < 100.0


def test_forwards_a_token_and_the_samplers_share(monkeypatch):
    def reg(den, com, acc):
        return {
            "sutro_block_row_forwards_total": {
                "series": {"denoise": den, "commit": com}},
            "sutro_block_tokens_total": {"series": {"accepted": acc}},
        }

    r = reading(CUT, [ATTRS], registry=(reg(100.0, 50.0, 10.0),
                                        reg(1700.0, 850.0, 3010.0)))
    assert bd_forwards_per_token.read(r) == pytest.approx(2400.0 / 3000.0)
    assert bd_forwards_per_token.read(reading(CUT, [ATTRS])) is None
    from perfbench import trace_parts

    monkeypatch.setattr(
        trace_parts, "seconds_by_part",
        lambda r, modules=None: {"mixer": 0.5, "ffn": 1.2, "head": 0.1,
                                 "sample": 0.2, None: 0.0})
    assert bd_sample_share_of_step.read(r) == pytest.approx(10.0)
    monkeypatch.setattr(trace_parts, "seconds_by_part", lambda r, m=None: None)
    assert bd_sample_share_of_step.read(r) is None


def test_a_program_or_a_configuration_without_blocks_reads_nothing(
    monkeypatch,
):
    """The parent's program (no span attr, no counter, no part under its
    ops) and another family's configuration (no ``block_length``): every
    reader returns None, none raises; nor on an untraced run."""
    from perfbench import trace_parts

    monkeypatch.setattr(trace_parts, "seconds_by_part", lambda r, m=None: None)
    mellum = json.loads((
        PERFBENCH / "configs/mellum2-12b-a2.5b-l8-v5e1.json"
    ).read_text())
    bare = {"steps": 8, "batch": 120, "avg_ctx": 600}
    for cfg, attrs in ((CUT, bare), (mellum, ATTRS)):
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
            assert m["workloads"] == [CELL]
            assert m["moves"] == "out_tokens_per_s_per_chip"
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == names | {
        "engine_host_us_per_row", "decode_step_device_ms",
        "prefill_device_us_per_token", "decode_row_steps_kept_share",
        "moe_expert_rows_max_over_mean",
    }
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "out_tokens_per_s_per_chip")
    assert CELL in e2e["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert cell["traffic"] == "generate-block-diffusion-jobs"
    assert cell["config"] == CUT["name"]
