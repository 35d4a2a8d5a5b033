"""``bytes_and_flops_ssm.py`` against the weights and the state pools the
program builds and against this PR's inventory (ISSUE 32), and the two
readers that a model with mamba layers brings, on hand-made readings."""

import functools
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops as bf
from perfbench import bytes_and_flops_ssm as ssm
from perfbench.layer_metrics import (
    ssm_hybrid_decode_hbm_roofline, state_slot_occupancy,
)
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import alloc_cache, state_bytes_per_slot
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS

PERFBENCH = Path(ssm.__file__).parent
WHOLE = json.loads(
    (PERFBENCH / "configs/granite-4.0-h-micro-v5e1.json").read_text())
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-granite-cpu.json").read_text())
CELL = "granite-4.0-h-micro.generate-short-jobs"


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [WHOLE, TINY], ids=["the whole model", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert ssm.param_count(cfg) == served(cfg["engine_key"])
    assert ssm.active_param_count(cfg) == ssm.param_count(cfg)     # dense


@pytest.mark.parametrize("cfg", [WHOLE, TINY], ids=["the whole model", "tiny"])
def test_the_state_a_sequence_is_the_pools_bytes_a_slot(cfg):
    mcfg = MODEL_CONFIGS[cfg["engine_key"]]
    ecfg = EngineConfig(**cfg["engine"])
    width = np.dtype(ecfg.activation_dtype).itemsize
    assert ssm.state_bytes_per_sequence(cfg, width) == state_bytes_per_slot(
        mcfg, ecfg)
    pools = jax.eval_shape(lambda: alloc_cache(mcfg, ecfg, 1 + 16))
    slots = pools.ssm.shape[1]
    assert slots == 1 + min(ecfg.decode_batch_size, 16)
    per_slot = (pools.ssm.size + pools.ssm_conv.size) * width // slots
    assert per_slot == ssm.state_bytes_per_sequence(cfg, width)
    assert pools.conv is None and pools.state_slot.shape == (17,)


def test_the_whole_model_is_the_issues_inventory():
    d = ssm.dims(WHOLE)
    assert (d["mamba_layers"], d["attn_layers"], d["L"]) == (36, 4, 40)
    assert ssm.param_count(WHOLE) == 3_191_396_096
    # a mamba layer with its FFN and both norms; an attention layer likewise
    assert ssm.mamba_mixer_params(d) + ssm.ffn_params(d) == 76_182_976
    assert ssm.attention_mixer_params(d) + ssm.ffn_params(d) == 60_821_504
    assert d["V"] * d["H"] == 205_520_896
    assert 6.38e9 < 2 * ssm.param_count(WHOLE) < 6.39e9
    # K/V over the four attention layers, not forty: 8 KB a token
    assert ssm.kv_bytes_per_token(WHOLE) == 8192
    assert bf.kv_bytes_per_token(WHOLE) == 10 * 8192       # what the other file counts
    # 64 heads x 64 x 128 and 3 columns of 4,352, over 36 layers, in bf16
    assert ssm.state_bytes_per_sequence(WHOLE) == 36 * (524_288 + 3 * 4352) * 2
    assert 38.6e6 < ssm.state_bytes_per_sequence(WHOLE) < 38.8e6
    assert WHOLE["reduced"] == [] and len(WHOLE["layer_types"]) == 40


def test_decode_bytes_and_flops_by_kind():
    step = functools.partial(ssm.decode_bytes_per_step, WHOLE, batch=128,
                             mean_ctx=350)
    full = step(state_rows=128)
    # weights (the embedding is read as the head), K/V, the state READ once
    assert full == (2 * ssm.param_count(WHOLE) + 128 * 8192 * 351
                    + 128 * ssm.state_bytes_per_sequence(WHOLE))
    assert step(state_rows=64) == full - 64 * ssm.state_bytes_per_sequence(WHOLE)
    assert 11.6e9 < full < 11.8e9
    with pytest.raises(TypeError):
        ssm.decode_bytes_per_step(WHOLE, batch=128, mean_ctx=350)   # no guess
    flops = ssm.forward_flops_per_token(WHOLE, ctx=0)
    # every weight once but the norms, biases and per-head scalars, and
    # the state's update and read
    d = ssm.dims(WHOLE)
    small = 36 * (d["conv_dim"] + 3 * 64 + d["I"] + d["H"]) + 4 * d["H"] + 41 * d["H"]
    assert flops == 2 * (ssm.param_count(WHOLE) - small + 36 * 2 * 4096 * 128)
    assert 6.3e9 < flops < 6.6e9
    with pytest.raises(ValueError, match="layer_types"):
        ssm.dims(dict(WHOLE, layer_types=["mamba"] * 39 + ["conv"]))
    with pytest.raises(ValueError, match="no routed experts"):
        ssm.dims(dict(WHOLE, num_local_experts=8))


def reading(cfg, span_attrs, step_s=0.03, registry=None):
    spans = [("decode_window", 1.0 + i, 1.5 + i, a) for i, a in enumerate(span_attrs)]
    reg = registry or ({}, {})
    r = types.SimpleNamespace(
        cfg=dict(cfg, engine={"param_dtype": "bfloat16",
                              "activation_dtype": "bfloat16"}),
        n_chips=1, spans=spans, window_spans=spans,
        trace={"module_s": {"jit__decode_multi_jit": {
            "s": 8 * step_s * max(len(spans), 1), "runs": max(len(spans), 1)}}},
        trace_span=(0.0, 100.0), peaks=lambda: bf.load_peaks("TPU v5 lite"),
        reg0=reg[0], reg1=reg[1], t0=0.0, t1=40.0,
    )
    r.spans_in_trace = lambda name: [s for s in spans if s[0] == name]
    return r


def test_the_ssm_roofline_reads_the_spans_and_the_ssm_counts():
    attrs = {"steps": 8, "batch": 128, "avg_ctx": 350, "state_rows": 128,
             "state_bytes": 128 * 38_688_768}
    got = ssm_hybrid_decode_hbm_roofline.read(reading(WHOLE, [attrs, attrs]))
    want = ssm.decode_bytes_per_step(WHOLE, batch=128, mean_ctx=350, state_rows=128)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.03)
    assert 45.0 < got < 50.0
    # a program whose spans carry no state rows (the parent), and a
    # configuration without mamba layers, read nothing; neither raises
    bare = {"steps": 8, "batch": 128, "avg_ctx": 350}
    assert ssm_hybrid_decode_hbm_roofline.read(reading(WHOLE, [bare])) is None
    for other in ("qwen3-4b-v5e1", "lfm2-24b-a2b-l10-v5e1"):
        cfg = json.loads((PERFBENCH / f"configs/{other}.json").read_text())
        assert ssm_hybrid_decode_hbm_roofline.read(reading(cfg, [attrs])) is None


def test_the_slot_occupancy_reads_the_gauge_and_the_spans():
    name = state_slot_occupancy.GAUGE
    assert state_slot_occupancy.read(reading(WHOLE, [])) is None    # no gauge
    reg = ({name: {"series": {"in_use": 96.0, "total": 128.0}}},
           {name: {"series": {"in_use": 128.0, "total": 128.0}}})
    assert state_slot_occupancy.read(
        reading(WHOLE, [], registry=reg)) == pytest.approx(87.5)
    spans = [{"steps": 8, "state_rows": 64}, {"steps": 8, "state_rows": 128}]
    assert state_slot_occupancy.read(
        reading(WHOLE, spans, registry=reg)) == pytest.approx(
            100.0 * (96 + 128 + 64 + 128) / 4 / 128)
    # spans of a program that counts no state rows are not read as zeros
    assert state_slot_occupancy.read(
        reading(WHOLE, [{"steps": 8}], registry=reg)) == pytest.approx(87.5)


def test_the_cell_is_listed_where_its_readers_find_something():
    bench = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "out_tokens_per_s_per_chip", "engine_host_us_per_row",
        "decode_step_device_ms", "prefill_device_us_per_token",
        "state_fallback_prefill_share", "ssm_hybrid_decode_hbm_roofline",
        "state_slot_occupancy",
    }
    for name in ("ssm_hybrid_decode_hbm_roofline", "state_slot_occupancy"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        mod = {"ssm_hybrid_decode_hbm_roofline": ssm_hybrid_decode_hbm_roofline,
               "state_slot_occupancy": state_slot_occupancy}[name]
        assert CELL in entry["workloads"]
        assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
                entry["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER,
                                    mod.MOVES)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "generate-short-jobs")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(bench["workloads"]) >= 5
