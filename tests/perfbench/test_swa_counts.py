"""``bytes_and_flops_swa.py`` against the weights the program builds and
against ISSUE 34's inventory, and the three readers that a model with
window and full attention layers brings, on hand-made readings."""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import bytes_and_flops as bf
from perfbench import bytes_and_flops_swa as swa
from perfbench.layer_metrics import (
    decode_kv_pages_fetched_over_needed, swa_moe_decode_hbm_roofline,
    window_kv_held_share,
)
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.perfbench.test_hybrid_counts import reading

PERFBENCH = Path(swa.__file__).parent
CUT = json.loads(
    (PERFBENCH / "configs/mellum2-12b-a2.5b-l8-v5e1.json").read_text()
)
TINY = json.loads(
    (PERFBENCH / "rehearsal/configs/tiny-mellum2-cpu.json").read_text()
)


def served(engine_key):
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, MODEL_CONFIGS[engine_key]),
        jax.random.PRNGKey(0),
    )
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("cfg", [CUT, TINY], ids=["the cut", "tiny"])
def test_the_counts_are_the_weights_the_runner_holds(cfg):
    assert swa.param_count(cfg) == served(cfg["engine_key"])


def test_the_cut_is_the_issues_inventory():
    d = swa.dims(CUT)
    assert (d["window_layers"], d["full_layers"], d["window"]) == (6, 2, 1024)
    # ISSUE 34: a layer 417,747,456, embedding and head 452,984,832, the
    # final norm; and 2 x 128 a layer for the QK norms assumed
    layer = 21_233_664 + 4_608 + 147_456 + 396_361_728
    assert layer == 417_747_456
    assert swa.attention_mixer_params(d) + swa.routed_ffn_params(d) == layer + 256
    assert swa.param_count(CUT) == 8 * layer + 452_984_832 + 2_304 + 8 * 256
    assert swa.param_count(CUT) == 3_794_966_784 + 2_048 == CUT["parameters"]
    assert 7.58e9 < 2 * swa.param_count(CUT) < 7.60e9
    # one token: 8 experts of 64 in each layer
    assert swa.active_param_count(CUT) == (
        swa.param_count(CUT) - 8 * 56 * 3 * 2304 * 896 - 98_304 * 2_304
    )
    # the whole published model: 28 layers, 24.3 GB, 2.4 B active
    types28 = ["sliding_attention"] * 3 + ["full_attention"]
    whole = dict(CUT, num_hidden_layers=28, layer_types=types28 * 7,
                 mlp_layer_types=["sparse"] * 28)
    assert swa.param_count(whole) == served("mellum2-12b-a2.5b")
    assert swa.param_count(whole) == 12_149_915_904 + 28 * 256
    assert 24.2e9 < 2 * swa.param_count(whole) < 24.4e9
    # 2.44 B with the embedding table, of which a token reads one row
    assert 2.43e9 < swa.active_param_count(whole) + 98_304 * 2_304 < 2.45e9
    with pytest.raises(ValueError, match="layer_types"):
        swa.dims(dict(CUT, layer_types=["conv"] * 8))
    with pytest.raises(ValueError, match="not routed"):
        swa.dims(dict(CUT, mlp_layer_types=["dense"] * 8))


def test_kv_by_kind_and_the_step_bytes():
    # 2 KB a token a layer; a sequence of 3,600: 2 full x 3,600 + 6 x 1,024
    assert swa.kv_bytes_per_token_layer(CUT) == 2048
    assert swa.kv_bytes_per_sequence(CUT, 3600) == (7200 + 6144) * 2048
    assert swa.kv_bytes_per_sequence(CUT, 500) == 8 * 500 * 2048
    # what the other counts file says of the same sequence: every layer whole
    assert bf.kv_bytes_per_token(CUT) == 8 * 2048
    step = functools.partial(
        swa.decode_bytes_per_step, CUT, batch=64, kv_tokens_full=1800,
        kv_tokens_window=1024,
    )
    full = step(experts_touched=64.0)
    weights = 2 * (swa.param_count(CUT) - 98_304 * 2_304)
    assert full == weights + 64 * 2048 * (2 * 1801 + 6 * 1025)
    assert step(experts_touched=32.0) == full - 2 * 8 * 32 * 3 * 2304 * 896
    assert 8.4e9 < full < 8.45e9          # ISSUE 34 reckoned 8.4 GB
    with pytest.raises(TypeError):
        swa.decode_bytes_per_step(CUT, batch=64, kv_tokens_full=1800,
                                  kv_tokens_window=1024)      # no guess
    flops0 = swa.forward_flops_per_token(CUT, ctx=0)
    assert flops0 == 2 * (swa.active_param_count(CUT) - 8 * (2 * 2304 + 256) - 2304)
    # past the window only the full layers' products grow
    grow = swa.forward_flops_per_token(CUT, 3000) - swa.forward_flops_per_token(CUT, 2000)
    assert grow == 2 * 2 * 32 * 128 * 2 * 1000


def test_the_roofline_reads_the_spans_and_the_swa_counts():
    attrs = {"steps": 8, "batch": 64, "avg_ctx": 1800, "kv_tokens_full": 1800.0,
             "kv_tokens_window": 1024.0, "experts_touched": 64.0,
             "expert_rows_max": 14.0, "expert_rows_mean": 8.0}
    got = swa_moe_decode_hbm_roofline.read(reading(CUT, [attrs, attrs]))
    want = swa.decode_bytes_per_step(
        CUT, batch=64, kv_tokens_full=1800, kv_tokens_window=1024,
        experts_touched=64.0)
    assert got == pytest.approx(100.0 * want / 819e9 / 0.02)
    assert 51.0 < got < 52.0
    # a program that keeps one pool writes no kv_tokens_window, and a
    # configuration without a window has nothing to count: neither raises
    bare = {"steps": 8, "batch": 64, "avg_ctx": 1800, "experts_touched": 64.0}
    assert swa_moe_decode_hbm_roofline.read(reading(CUT, [bare])) is None
    other = json.loads(
        (PERFBENCH / "configs/lfm2-24b-a2b-l10-v5e1.json").read_text())
    assert swa_moe_decode_hbm_roofline.read(reading(other, [attrs])) is None


def test_the_page_readers_read_the_counters_and_nothing_without_them():
    f, n = (decode_kv_pages_fetched_over_needed.FETCHED,
            decode_kv_pages_fetched_over_needed.NEEDED)
    h, w = window_kv_held_share.HELD, window_kv_held_share.WHOLE
    gauge = {"series": {"window,used": 600.0, "window,free": 552.0}}
    # the parent: the fetch counters but no pool a kind, no held counters
    parent = ({f: {"series": {"": 10.0}}, n: {"series": {"": 9.0}}},
              {f: {"series": {"": 110.0}}, n: {"series": {"": 99.0}}})
    assert decode_kv_pages_fetched_over_needed.read(
        reading(CUT, [], registry=parent)) is None
    assert window_kv_held_share.read(reading(CUT, [], registry=parent)) is None
    assert window_kv_held_share.read(reading(CUT, [])) is None
    a = {f: {"series": {"": 100.0}}, n: {"series": {"": 90.0}},
         h: {"series": {"": 1000.0}}, w: {"series": {"": 2000.0}},
         "sutro_kv_pages": gauge}
    b = {f: {"series": {"": 1200.0}}, n: {"series": {"": 1090.0}},
         h: {"series": {"": 7000.0}}, w: {"series": {"": 12000.0}},
         "sutro_kv_pages": gauge}
    assert decode_kv_pages_fetched_over_needed.read(
        reading(CUT, [], registry=(a, b))) == pytest.approx(1.1)
    assert window_kv_held_share.read(
        reading(CUT, [], registry=(a, b))) == pytest.approx(0.6)
    # no decode dispatch in the window: nothing to divide by
    assert decode_kv_pages_fetched_over_needed.read(
        reading(CUT, [], registry=(b, b))) is None
    assert window_kv_held_share.read(reading(CUT, [], registry=(b, b))) is None
