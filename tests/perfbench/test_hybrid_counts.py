"""``bytes_and_flops_hybrid.py`` against the weights the program builds
and against this PR's inventory (ISSUE 28), and the three readers that
a model with layers of several kinds brings, on hand-made readings."""

import functools
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops as bf
from perfbench import bytes_and_flops_hybrid as hy
from perfbench.layer_metrics import (
    hybrid_moe_decode_hbm_roofline, moe_expert_rows_max_over_mean,
    state_fallback_prefill_share,
)
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS

PERFBENCH = Path(hy.__file__).parent
CUT = json.loads((PERFBENCH / "configs/lfm2-24b-a2b-l10-v5e1.json").read_text())
TINY = json.loads((PERFBENCH / "rehearsal/configs/tiny-lfm2-cpu.json").read_text())


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert hy.param_count(cfg) == served(cfg["engine_key"])


def test_the_cut_is_the_issues_inventory():
    d = hy.dims(CUT)
    assert (d["conv_layers"], d["attn_layers"], d["dense_layers"],
            d["routed_layers"]) == (8, 2, 2, 8)
    assert hy.param_count(CUT) == 5_267_090_176
    assert 8 * 64 * 3 * 2048 * 1536 == 4_831_838_208          # the experts
    assert 8 * hy.conv_mixer_params(d) == 134_266_880 + 8 * 2048
    assert 2 * hy.dense_ffn_params(d) == 144_703_488 + 2 * 2048
    # one token: 4 experts of 64 in each routed layer
    assert hy.active_param_count(CUT) == hy.param_count(CUT) - 8 * 60 * 3 * 2048 * 1536
    # K/V over the two attention layers, not ten: 4 KB a token
    assert hy.kv_bytes_per_token(CUT) == 4096
    assert bf.kv_bytes_per_token(CUT) == 5 * 4096              # what the other file counts
    assert hy.state_bytes_per_sequence(CUT) == 65_536
    # the whole published model: 40 layers, 38 routed
    whole = dict(CUT, num_hidden_layers=40, layer_types=(
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
        + ["full_attention", "conv"]))
    assert hy.param_count(whole) == served("lfm2-24b-a2b")
    assert 47.5e9 < 2 * hy.param_count(whole) < 47.8e9


def test_decode_bytes_and_flops_by_kind():
    step = functools.partial(hy.decode_bytes_per_step, CUT, batch=256, mean_ctx=350)
    full = step(experts_touched=64.0)
    # weights (the embedding is read as the head), K/V, state both ways
    assert full == 2 * hy.param_count(CUT) + 256 * 4096 * 351 + 2 * 256 * 65_536
    assert step(experts_touched=32.0) == full - 2 * 8 * 32 * 3 * 2048 * 1536
    assert 10.8e9 < full < 11.0e9
    with pytest.raises(TypeError):
        hy.decode_bytes_per_step(CUT, batch=256, mean_ctx=350)   # no guess
    flops = hy.forward_flops_per_token(CUT, ctx=0)
    assert flops == 2 * (hy.active_param_count(CUT)
                         - 20 * 2048 - 2048 - 4 * 64 - 8 * 64)
    with pytest.raises(ValueError, match="layer_types"):
        hy.dims(dict(CUT, layer_types=["conv"] * 9 + ["mamba"]))


def reading(cfg, span_attrs, step_s=0.02, registry=None, prompt=(0, 0)):
    spans = [("decode_window", 1.0 + i, 1.5 + i, a) for i, a in enumerate(span_attrs)]
    reg = registry or ({}, {})
    r = types.SimpleNamespace(
        cfg=dict(cfg, engine={"param_dtype": "bfloat16"}), n_chips=1, spans=spans,
        trace={"module_s": {"jit__decode_multi_jit": {
            "s": 8 * step_s * max(len(spans), 1), "runs": max(len(spans), 1)}}},
        trace_span=(0.0, 100.0), peaks=lambda: bf.load_peaks("TPU v5 lite"),
        reg0=reg[0], reg1=reg[1], t0=0.0, t1=40.0,
        log=types.SimpleNamespace(cumulative_tokens=lambda which: [
            (1.0, prompt[0]), (39.0, prompt[1])]),
    )
    r.spans_in_trace = lambda name: [s for s in spans if s[0] == name]
    r.counter_delta = lambda name, key="": float(
        (reg[1].get(name) or {}).get("series", {}).get(key, 0.0)
        - (reg[0].get(name) or {}).get("series", {}).get(key, 0.0))
    return r


def test_the_hybrid_roofline_reads_the_spans_and_the_hybrid_counts():
    attrs = {"steps": 8, "batch": 256, "avg_ctx": 350, "experts_touched": 64.0,
             "expert_rows_max": 27.0, "expert_rows_mean": 16.0}
    got = hybrid_moe_decode_hbm_roofline.read(reading(CUT, [attrs, attrs]))
    want = hy.decode_bytes_per_step(CUT, batch=256, mean_ctx=350, experts_touched=64.0)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.02)
    assert 60.0 < got < 70.0
    # a program whose spans do not count its routing, and a
    # configuration of one kind of layer, read nothing; neither raises
    bare = {"steps": 8, "batch": 256, "avg_ctx": 350}
    assert hybrid_moe_decode_hbm_roofline.read(reading(CUT, [bare])) is None
    dense = json.loads((PERFBENCH / "configs/qwen3-4b-v5e1.json").read_text())
    assert hybrid_moe_decode_hbm_roofline.read(reading(dense, [attrs])) is None
    assert moe_expert_rows_max_over_mean.read(reading(CUT, [bare])) is None
    assert moe_expert_rows_max_over_mean.read(
        reading(CUT, [attrs, dict(attrs, expert_rows_max=37.0), bare])
    ) == pytest.approx(2.0)


def test_the_fallback_share_reads_every_reason_and_nothing_without_the_counter():
    name = state_fallback_prefill_share.COUNTER
    assert state_fallback_prefill_share.read(reading(CUT, [])) is None
    none_yet = ({name: {"series": {}}}, {name: {"series": {}}})
    assert state_fallback_prefill_share.read(
        reading(CUT, [], registry=none_yet, prompt=(1000, 21000))) == 0.0
    assert state_fallback_prefill_share.read(
        reading(CUT, [], registry=none_yet)) == 0.0     # nothing submitted
    some = ({name: {"series": {"hibernated_tail_page": 10.0}}},
            {name: {"series": {"hibernated_tail_page": 110.0,
                               "tier_payload_without_state": 100.0}}})
    assert state_fallback_prefill_share.read(
        reading(CUT, [], registry=some, prompt=(1000, 21000))) == pytest.approx(1.0)
