"""``bytes_and_flops_laguna``'s counts against the tree ``init_params``
builds and the configuration files' ``parameters``, a decode step's bytes
and a prefill's operations by hand at one size, and the family's device
readers on a made-up reading: each a share under 100 % of what the
numbers say, each silent on a program that lacks the spans."""

import functools
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import bytes_and_flops_laguna as counts
from perfbench.layer_metrics import (
    laguna_moe_decode_hbm_roofline, laguna_paged_decode_hbm_roofline,
    laguna_prefill_mxu_roofline, prefill_padded_token_share,
)
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS

REPO = Path(__file__).resolve().parents[2]
CHIP = json.loads(
    (REPO / "perfbench/configs/laguna-s-2.1-l9-ep8-v5e1.json").read_text())
TINY = json.loads(
    (REPO / "perfbench/rehearsal/configs/tiny-laguna-cpu.json").read_text())


@pytest.mark.parametrize("doc", [CHIP, TINY], ids=lambda d: d["name"])
def test_param_count_is_the_tree_init_params_builds_and_the_files(doc):
    shapes = jax.eval_shape(
        functools.partial(
            transformer.init_params, MODEL_CONFIGS[doc["engine_key"]],
            dtype=jnp.bfloat16),
        jax.random.PRNGKey(0),
    )
    built = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert counts.param_count(doc) == built == doc["parameters"]


def test_the_published_model_counts_what_the_catalog_says():
    whole = dict(
        CHIP, num_hidden_layers=48, vocab_size=100_352, share={},
        layer_types=["full_attention" if l % 4 == 0 else "sliding_attention"
                     for l in range(48)],
        mlp_layer_types=["dense"] + ["sparse"] * 47,
        num_attention_heads_per_layer=[48 if l % 4 == 0 else 72
                                       for l in range(48)],
    )
    assert counts.param_count(whole) == CHIP["published"]["parameters"]
    assert 117e9 < counts.param_count(whole) < 118e9      # "about 118 B"


def test_a_decode_steps_bytes_by_hand():
    d = counts.dims(CHIP)
    assert (d["NH_window"], d["NH_full"], d["held"], d["E"]) == (72, 48, 32, 256)
    H = 3072
    window = 2 * H * 9216 + 2 * H * 1024 + H * 72 + 256 + H
    full = 2 * H * 6144 + 2 * H * 1024 + H * 48 + 256 + H
    assert (counts.mixer_params(d, 72), counts.mixer_params(d, 48)) == (
        window, full) == (63_139_072, 44_190_976)
    rest = H * 256 + 3 * H * 1024 + 2 * H
    expert = 3 * H * 1024
    dense = 3 * H * 12_288 + H
    touched = 20.0
    weights = (6 * window + 3 * full + dense + 8 * (rest + touched * expert)
               + H + H * 12_544)
    assert counts.decode_weight_params(CHIP, touched) == weights
    # K and V of 8 heads of 128 in bf16: 4 KB a token a layer
    assert counts.kv_bytes_per_token_layer(CHIP) == 4096
    kv = 128 * 4096 * (3 * (1800.0 + 1) + 6 * (440.0 + 1))
    got = counts.decode_bytes_per_step(
        CHIP, batch=128, kv_tokens_full=1800.0, kv_tokens_window=440.0,
        experts_touched=touched)
    assert got == 2 * weights + kv
    assert counts.decode_kv_bytes(
        CHIP, batch=128, kv_tokens_full=1800.0, kv_tokens_window=440.0,
        written=0.0) == 128 * 4096 * (3 * 1800.0 + 6 * 440.0)


def test_a_prefills_operations_go_by_real_tokens_and_the_mask():
    assert counts.causal_pairs(4) == 10 and counts.causal_pairs(4, 8) == 10
    assert counts.causal_pairs(1000, 512) == 512 * 513 / 2 + 488 * 512
    H, n = 3072, 1000.0
    per_token = (
        6 * (2 * H * 9216 + 2 * H * 1024 + H * 72)
        + 3 * (2 * H * 6144 + 2 * H * 1024 + H * 48) + 3 * H * 12_288
        + 8 * (H * 256 + 3 * H * 1024 + H + 10 * 32 / 256 * 3 * H * 1024)
    )
    attention = 2 * 128 * (
        3 * 48 * n * (n + 1) / 2 + 6 * 72 * counts.causal_pairs(n, 512))
    want = 2 * (n * per_token + attention + H * 12_544)
    assert counts.prefill_flops_per_row(CHIP, n) == want
    assert counts.prefill_flops(CHIP, [n, 0, n]) == 2 * want
    # a long row's attention goes by its square in the full layers alone
    long, short = (counts.prefill_flops_per_row(CHIP, x) for x in (7000, 350))
    assert 20 < long / short < 30


def reading(spans, op_s=None, reg=None, step=(0.016, 100)):
    trace = None if op_s is None else {
        "op_s": op_s, "busy_s": 1.0, "window_s": 1.0,
    }
    r = types.SimpleNamespace(
        cfg=CHIP, trace=trace, n_chips=1, reg0={}, reg1=reg or {},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        spans_in_trace=lambda name: [s for s in spans if s[0] == name],
    )
    r.counter_delta = lambda name, key="": float(
        (reg or {}).get(name, {}).get("series", {}).get(key, 0.0))
    return r


def test_the_readers_are_shares_of_what_the_spans_say(monkeypatch):
    attrs = dict(batch=128, steps=8, kv_tokens_full=1800.0,
                 kv_tokens_window=440.0, experts_touched=20.0)
    spans = [("decode_window", 0.0, 1.0, attrs),
             ("prefill", 0.0, 0.1, {"row_tokens": [7000], "tokens": 7000}),
             ("prefill", 0.2, 0.3, {"row_tokens": [300, 500], "tokens": 800})]
    for mod in (laguna_moe_decode_hbm_roofline,
                laguna_paged_decode_hbm_roofline):
        # 100 steps in 1.6 s of step time
        monkeypatch.setattr(mod, "steps_and_seconds", lambda r: (1.6, 100))
    r = reading(spans, op_s={"jit/paged_decode_attention/x": 0.6,
                             "fusion.7": 0.9})
    total = counts.decode_bytes_per_step(CHIP, **{
        k: v for k, v in attrs.items() if k != "steps"})
    whole = laguna_moe_decode_hbm_roofline.read(r)
    assert whole == pytest.approx(100 * total / 819e9 / 0.016)
    assert 50 < whole < 100
    kv = counts.decode_kv_bytes(
        CHIP, batch=128, kv_tokens_full=1800.0, kv_tokens_window=440.0,
        written=0.0)
    paged = laguna_paged_decode_hbm_roofline.read(r)
    assert paged == pytest.approx(100 * 100 * kv / 819e9 / 0.6)
    assert 0 < paged < 100
    import perfbench.trace_reduce as trace_reduce

    monkeypatch.setattr(
        trace_reduce, "module_seconds", lambda trace, pat: (0.25, 3))
    mxu = laguna_prefill_mxu_roofline.read(r)
    flops = counts.prefill_flops(CHIP, [7000]) + counts.prefill_flops(
        CHIP, [300, 500])
    assert mxu == pytest.approx(100 * flops / 197e12 / 0.25) and mxu < 100
    # a program that lacks the spans (the parent commit's): nothing
    bare = reading([("decode_window", 0, 1, {"batch": 128, "steps": 8}),
                    ("prefill", 0, 1, {"tokens": 7000, "batch": 1})],
                   op_s={"paged_decode_attention": 0.5})
    assert laguna_moe_decode_hbm_roofline.read(bare) is None
    assert laguna_paged_decode_hbm_roofline.read(bare) is None
    assert laguna_prefill_mxu_roofline.read(bare) is None
    assert prefill_padded_token_share.read(bare) is None
    # and a configuration of another family
    other = reading(spans, op_s={"paged_decode_attention": 0.5})
    other.cfg = {"engine": {}}
    assert laguna_moe_decode_hbm_roofline.read(other) is None
    reg = {"sutro_prefill_tokens_total": {
        "series": {"real": 7800.0, "padded": 2200.0}}}
    assert prefill_padded_token_share.read(reading([], reg=reg)) == 0.22
