"""Counts for a routed block (``bytes_and_flops``), worked by hand for the
published Qwen3-30B-A3B keys, and dense counts unchanged to the byte.
(``test_benchmark_file.py`` holds a configuration file's routed keys to
the program's preset.)"""

import json
import types
from pathlib import Path

import pytest

from perfbench import bytes_and_flops as bf
from perfbench.layer_metrics import decode_hbm_roofline

PERFBENCH = Path(bf.__file__).resolve().parent
# https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/config.json
A3B = {
    "hidden_size": 2048, "num_hidden_layers": 48, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
    "vocab_size": 151936, "tie_word_embeddings": False, "num_experts": 128,
    "num_experts_per_tok": 8, "moe_intermediate_size": 768,
    "norm_topk_prob": True,
}
ATTN = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048      # q, k, v, o
ROUTER, EXPERT = 2048 * 128, 3 * 2048 * 768
NORMS = 2 * 2048 + 2 * 128
EMBED = 151936 * 2048


def test_qwen3_30b_a3b_parameters():
    assert bf.layer_matmul_params(A3B) == ATTN + ROUTER + 128 * EXPERT
    assert bf.param_count(A3B) == 48 * (ATTN + ROUTER + 128 * EXPERT + NORMS) + 2 * EMBED + 2048
    assert bf.param_count(A3B) == 30_532_122_624          # "30.5 B"
    # 8 of 128 experts a token: "A3B"
    assert bf.active_param_count(A3B) == 48 * (ATTN + ROUTER + 8 * EXPERT + NORMS) + 2 * EMBED + 2048
    assert bf.active_param_count(A3B) == 3_353_032_704
    # the published dense width (6144) is unused by a routed block
    assert bf.param_count(dict(A3B, intermediate_size=1)) == bf.param_count(A3B)


def test_routed_forward_flops_count_the_router_and_k_experts():
    matmul = 2 * (48 * (ATTN + ROUTER + 8 * EXPERT) + EMBED)
    assert bf.forward_flops_per_token(A3B, ctx=0) == matmul
    assert bf.forward_flops_per_token(A3B, ctx=1000) - matmul == 4 * 48 * 32 * 128 * 1000


def test_routed_decode_bytes_follow_experts_touched():
    kv = 128 * bf.kv_bytes_per_token(A3B) * 501
    assert bf.kv_bytes_per_token(A3B) == 48 * 2 * 4 * 128 * 2
    # every expert touched: all the weights but the gathered embedding, + K/V
    everything = bf.decode_bytes_per_step(A3B, batch=128, mean_ctx=500, experts_touched=128)
    assert everything == 2 * (bf.param_count(A3B) - EMBED) + kv
    some = bf.decode_bytes_per_step(A3B, batch=128, mean_ctx=500, experts_touched=97.5)
    assert everything - some == pytest.approx(2 * 48 * 30.5 * EXPERT)
    none = bf.decode_bytes_per_step(A3B, batch=128, mean_ctx=500, experts_touched=0)
    assert none == 2 * (48 * (ATTN + ROUTER + NORMS) + 2048 + EMBED) + kv
    with pytest.raises(ValueError):        # never a guess from uniform routing
        bf.decode_bytes_per_step(A3B, batch=128, mean_ctx=500)


@pytest.mark.parametrize("name", ["qwen3-4b-v5e1", "qwen3-8b-v5e4-tp4"])
def test_dense_counts_are_what_they_were(name):
    cfg = json.loads((PERFBENCH / "configs" / f"{name}.json").read_text())
    H, L, F, V = (cfg[k] for k in ("hidden_size", "num_hidden_layers",
                                   "intermediate_size", "vocab_size"))
    NH, KVH, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    block = H * NH * Dh + 2 * H * KVH * Dh + NH * Dh * H + 3 * H * F
    assert bf.layer_matmul_params(cfg) == block
    head = 0 if cfg["tie_word_embeddings"] else H * V
    assert bf.param_count(cfg) == L * (block + 2 * H + 2 * Dh) + V * H + H + head
    assert bf.active_param_count(cfg) == bf.param_count(cfg)
    assert bf.decode_weight_params(cfg) == L * (block + 2 * H + 2 * Dh) + H + H * V
    for touched in (None, 3.0):            # a dense block has no experts to touch
        assert bf.decode_bytes_per_step(
            cfg, batch=64, mean_ctx=350, experts_touched=touched
        ) == 2 * bf.decode_weight_params(cfg) + 64 * bf.kv_bytes_per_token(cfg) * 351
    assert bf.forward_flops_per_token(cfg, ctx=10) == 2 * (L * block + H * V) + 4 * L * NH * Dh * 10


def reading(cfg, span_attrs, step_s=0.02):
    """What ``decode_hbm_roofline.read`` looks at: one decode program of
    8 steps in the trace and the ``decode_window`` spans beside it."""
    spans = [("decode_window", 1.0 + i, 1.5 + i, a) for i, a in enumerate(span_attrs)]
    r = types.SimpleNamespace(
        cfg=dict(cfg, engine={"param_dtype": "bfloat16"}), n_chips=1, spans=spans,
        trace={"module_s": {"jit__decode_multi_jit": {
            "s": 8 * step_s * len(spans), "runs": len(spans)}}},
        trace_span=(0.0, 100.0), peaks=lambda: bf.load_peaks("TPU v5 lite"),
    )
    r.spans_in_trace = lambda name: [s for s in spans if s[0] == name]
    return r


def test_the_roofline_reader_takes_experts_touched_from_the_spans():
    attrs = {"steps": 8, "batch": 128, "avg_ctx": 500}
    small = dict(A3B, num_hidden_layers=8)
    # a routed configuration whose spans do not say reads nothing
    assert decode_hbm_roofline.read(reading(small, [attrs, attrs])) is None
    assert decode_hbm_roofline.read(
        reading(small, [dict(attrs, experts_touched=100), attrs])) is None
    got = decode_hbm_roofline.read(reading(
        small, [dict(attrs, experts_touched=90), dict(attrs, experts_touched=110)]))
    want = bf.decode_bytes_per_step(small, batch=128, mean_ctx=500, experts_touched=100)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.02)
    # a dense configuration reads as it always did, spans unchanged
    dense = json.loads((PERFBENCH / "configs/qwen3-4b-v5e1.json").read_text())
    got = decode_hbm_roofline.read(reading(dense, [dict(attrs, batch=64, avg_ctx=350)]))
    assert got == pytest.approx(
        100.0 * bf.decode_bytes_per_step(dense, batch=64, mean_ctx=350) / 819e9 / 0.02)
