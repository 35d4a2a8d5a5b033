"""``bytes_and_flops_sambay``'s counts against the tree ``init_params``
builds and the configuration files' ``parameters``, a decode step's bytes
and a prefill's operations by hand at one size, and the family's readers
on a made-up reading: each a share under 100 % of what the numbers say,
each silent on a program that lacks the spans or the counter."""

import functools
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import bytes_and_flops_sambay as counts
from perfbench.layer_metrics import (
    decode_shared_kv_read_share, sambay_decode_hbm_roofline,
    sambay_paged_decode_hbm_roofline, sambay_prefill_mxu_roofline,
    sambay_state_step_hbm_roofline,
)
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS

REPO = Path(__file__).resolve().parents[2]
CHIP = json.loads((
    REPO / "perfbench/configs/phi-4-mini-flash-reasoning-v5e1.json").read_text())
TINY = json.loads(
    (REPO / "perfbench/rehearsal/configs/tiny-phi4flash-cpu.json").read_text())


@pytest.mark.parametrize("doc", [CHIP, TINY], ids=lambda d: d["name"])
def test_param_count_is_the_tree_init_params_builds_and_the_files(doc):
    shapes = jax.eval_shape(
        functools.partial(
            transformer.init_params, MODEL_CONFIGS[doc["engine_key"]],
            dtype=jnp.bfloat16),
        jax.random.PRNGKey(0),
    )
    built = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert counts.param_count(doc) == built
    assert doc.get("parameters", built) == built
    # and a kind at a time, as the program stacks them
    by_kind = counts.params_by_kind(doc)
    stacks = shapes["layers"]
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))  # noqa: E731
    assert by_kind["mamba1"] == size(stacks["mamba1"])
    assert by_kind["attention_with_kv"] == size(stacks["swa"]) + size(stacks["attn"])
    assert by_kind["cross"] == size(stacks["cross"])
    assert by_kind["memory_unit"] == size(stacks["gmu"])
    assert by_kind["mlp"] == size(stacks["dense"])


def test_the_published_model_counts_what_the_issue_counted():
    assert CHIP["parameters"] == 3_852_562_944          # "3,852.6 M"
    by_kind = counts.params_by_kind(CHIP)
    assert by_kind["embedding"] == 200_064 * 2_560
    assert by_kind["mamba1"] == 9 * 41_246_720
    assert by_kind["attention_with_kv"] == 9 * 19_673_984
    assert by_kind["cross"] == 7 * 13_117_824
    assert by_kind["memory_unit"] == 7 * 26_219_520
    assert by_kind["mlp"] == 32 * 78_648_320
    assert CHIP["reduced"] == [] and "numbers" not in CHIP
    assert counts.layout(32, 2)[16:20] == ("mamba1", "attn", "gmu", "cross")


def test_a_decode_steps_bytes_by_hand():
    # K and V of 20 heads of 64 in bf16: 5,120 B a token a layer
    assert counts.kv_bytes_per_token_layer(CHIP) == 5120
    kv = 128 * 5120 * (8 * 800.0 + 8 * 480.0 + 9)
    assert counts.decode_kv_bytes(
        CHIP, batch=128, kv_tokens_full=800.0, kv_tokens_window=480.0) == kv
    # each reader counted: the spans' own numbers take the file's place
    assert counts.decode_kv_bytes(
        CHIP, batch=128, kv_tokens_full=800.0, kv_tokens_window=480.0,
        kv_readers_full=8, kv_readers_window=8, written=0.0,
    ) == 128 * 5120 * (8 * 800.0 + 8 * 480.0)
    # a slot: [16, 5120] and 3 conv columns of 5,120 in bf16, nine
    # layers, read and written
    state = 2 * 128 * 9 * (16 + 3) * 5120 * 2
    assert counts.state_bytes_per_step(CHIP, batch=128) == state
    got = counts.decode_bytes_per_step(
        CHIP, batch=128, kv_tokens_full=800.0, kv_tokens_window=480.0)
    assert got == 2 * CHIP["parameters"] + kv + state
    assert 14.5e9 < got < 15.5e9                        # the issue's 15.0 GB


def test_a_prefills_operations_go_by_real_tokens_and_the_models_products():
    assert counts.causal_pairs(1000, 512) == 512 * 513 / 2 + 488 * 512
    H, I, n = 2560, 5120, 1000.0
    per_token = (
        32 * 3 * H * 10_240
        + 9 * (H * 2 * I + I * 4 + I * 192 + 160 * I + 3 * 16 * I + I * H)
        + 9 * (2 * H * 2560 + 2 * H * 1280) + 7 * 2 * H * 2560 + 7 * 2 * H * I
    )
    # 20 differential heads: two QK^T of 64 and two PV of 128 a pair
    attention = 20 * (2 * 64 + 2 * 128) * (
        8 * n * (n + 1) / 2 + 8 * counts.causal_pairs(n, 512))
    want = 2 * (n * per_token + attention + H * 200_064)
    assert counts.prefill_flops_per_row(CHIP, n) == want
    assert counts.prefill_flops(CHIP, [n, 0, n]) == 2 * want
    # about 6.7 GFLOP a token at the traffic's mean prompt
    assert 6.5e9 < counts.prefill_flops_per_row(CHIP, 260) / 260 < 7.0e9


def reading(spans, op_s=None, reg=None, cfg=CHIP):
    trace = None if op_s is None else {
        "op_s": op_s, "busy_s": 1.0, "window_s": 1.0,
    }
    r = types.SimpleNamespace(
        cfg=cfg, trace=trace, n_chips=1, reg0={}, reg1=reg or {},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        spans_in_trace=lambda name: [s for s in spans if s[0] == name],
    )
    r.counter_delta = lambda name, key="": float(
        (reg or {}).get(name, {}).get("series", {}).get(key, 0.0))
    return r


def test_the_readers_are_shares_of_what_the_spans_say(monkeypatch):
    attrs = dict(batch=128, steps=8, kv_tokens_full=800.0,
                 kv_tokens_window=480.0, kv_readers_full=8,
                 kv_readers_window=8, state_layers=9)
    spans = [("decode_window", 0.0, 1.0, attrs),
             ("prefill", 0.0, 0.1, {"row_tokens": [600], "tokens": 600}),
             ("prefill", 0.2, 0.3, {"row_tokens": [130], "tokens": 130})]
    for mod in (sambay_decode_hbm_roofline, sambay_paged_decode_hbm_roofline,
                sambay_state_step_hbm_roofline):
        # 100 steps in 2.6 s of step time
        monkeypatch.setattr(mod, "steps_and_seconds", lambda r: (2.6, 100))
    monkeypatch.setattr(
        sambay_state_step_hbm_roofline, "scope_seconds", lambda r: 0.12)
    r = reading(spans, op_s={"jit/paged_decode_attention/x": 1.1,
                             "fusion.7": 0.9})
    step = {k: v for k, v in attrs.items() if k != "steps"}
    whole = sambay_decode_hbm_roofline.read(r)
    assert whole == pytest.approx(
        100 * counts.decode_bytes_per_step(CHIP, **step) / 819e9 / 0.026)
    assert 50 < whole < 100
    kv = counts.decode_kv_bytes(
        CHIP, written=0.0,
        **{k: v for k, v in step.items() if k != "state_layers"})
    paged = sambay_paged_decode_hbm_roofline.read(r)
    assert paged == pytest.approx(100 * 100 * kv / 819e9 / 1.1)
    assert 0 < paged < 100
    state = sambay_state_step_hbm_roofline.read(r)
    assert state == pytest.approx(100 * 100 * counts.state_bytes_per_step(
        CHIP, batch=128, state_layers=9) / 819e9 / 0.12)
    assert 0 < state < 100
    import perfbench.trace_reduce as trace_reduce

    monkeypatch.setattr(
        trace_reduce, "module_seconds", lambda trace, pat: (0.06, 2))
    mxu = sambay_prefill_mxu_roofline.read(r)
    flops = counts.prefill_flops(CHIP, [600]) + counts.prefill_flops(CHIP, [130])
    assert mxu == pytest.approx(100 * flops / 197e12 / 0.06) and mxu < 100
    # a program that lacks the spans (the parent commit's): nothing
    bare = reading([("decode_window", 0, 1, {"batch": 128, "steps": 8}),
                    ("prefill", 0, 1, {"tokens": 7000, "batch": 1})],
                   op_s={"paged_decode_attention": 0.5})
    for mod in (sambay_decode_hbm_roofline, sambay_paged_decode_hbm_roofline,
                sambay_state_step_hbm_roofline, sambay_prefill_mxu_roofline):
        assert mod.read(bare) is None
    assert decode_shared_kv_read_share.read(bare) is None
    # and a configuration of another family
    other = reading(spans, op_s={"paged_decode_attention": 0.5},
                    cfg={"engine": {}, "model_type": "laguna"})
    for mod in (sambay_decode_hbm_roofline, sambay_paged_decode_hbm_roofline,
                sambay_state_step_hbm_roofline, sambay_prefill_mxu_roofline):
        assert mod.read(other) is None
    # 7 x full over 8 x full + 8 x window
    reg = {"sutro_kv_read_tokens_total": {"series": {
        "full,own": 800.0, "full,shared": 5600.0, "window,own": 3840.0}}}
    assert decode_shared_kv_read_share.read(
        reading([], reg=reg)) == pytest.approx(100 * 5600 / 10240)
    # a model none of whose layers reads another's: the counter stands
    zero = {"sutro_kv_read_tokens_total": {"series": {}}}
    assert decode_shared_kv_read_share.read(reading([], reg=zero)) is None
