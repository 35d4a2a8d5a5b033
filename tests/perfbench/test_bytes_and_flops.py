"""bytes_and_flops against numbers worked by hand for Qwen3-4B, and the
peaks table's refusal of a device it does not know."""

import json
from pathlib import Path

import pytest

from perfbench import bytes_and_flops as bf

REPO = Path(__file__).resolve().parents[2]
Q4 = json.loads((REPO / "perfbench/configs/qwen3-4b-v5e1.json").read_text())
Q8 = json.loads((REPO / "perfbench/configs/qwen3-8b-v5e4-tp4.json").read_text())


def test_qwen3_4b_parameters():
    # per block: q 2560x4096, k and v 2560x1024 each, o 4096x2560,
    # gate/up/down 3 x 2560x9728
    assert bf.layer_matmul_params(Q4) == (
        10_485_760 + 2 * 2_621_440 + 10_485_760 + 74_711_040
    )
    # + two 2560 norms and two 128 QK norms a block; embedding
    # 151,936 x 2560 (tied head); final norm
    assert bf.param_count(Q4) == 36 * (100_925_440 + 5_376) + 388_956_160 + 2_560
    assert bf.param_count(Q4) == 4_022_468_096       # "4.02 B", 8.04 GB bf16


def test_qwen3_8b_is_untied():
    tied = dict(Q8, tie_word_embeddings=True)
    assert bf.param_count(Q8) - bf.param_count(tied) == 4096 * 151_936
    # a decode step reads the head but only gathers the embedding
    assert bf.param_count(Q8) - bf.decode_weight_params(Q8) == 4096 * 151_936
    assert bf.decode_weight_params(Q4) == bf.param_count(Q4)
    assert 2 * bf.param_count(Q8) == pytest.approx(16.38e9, rel=1e-3)


def test_kv_bytes():
    # 36 layers x (K and V) x 8 heads x 128 x 2 bytes
    assert bf.kv_bytes_per_token(Q4) == 147_456


def test_decode_bytes_per_step_at_the_issue_example():
    # batch 64 at ~350 tokens of context: 8.04 GB of weights + 3.3 GB of KV
    total = bf.decode_bytes_per_step(Q4, batch=64, mean_ctx=350)
    assert total == 2 * 4_022_468_096 + 64 * 147_456 * 351
    assert total / 819e9 == pytest.approx(0.01387, rel=1e-3)   # 13.9 ms


def test_forward_flops_per_token():
    matmul = 2 * (36 * 100_925_440 + 2560 * 151_936)
    assert bf.forward_flops_per_token(Q4, ctx=0) == matmul
    assert bf.forward_flops_per_token(Q4, ctx=1000) - matmul == (
        4 * 36 * 32 * 128 * 1000
    )


def test_known_device_has_the_published_peaks():
    p = bf.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        bf.load_peaks(kind)
