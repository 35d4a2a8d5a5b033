"""The index-key pool (engine/kvcache.py): a SECOND pool of a latent
layer that has an indexer, ``index_head_dim`` wide, on the latent pool's
page table; a page's bytes and the pool's size from
``ModelConfig.pool_row_widths`` (the one place that says what a token of
a layer keeps); the write of a chunk's index keys through the ``(k, v)``
pair the benchmark hands ``write_kv``; the kernel's in-place write at
the second width, interpreted; and each thing that does not work yet
refusing by name, or falling back and counting under a reason of its own.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine import kvcache
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.models.transformer import MixedChunk
from sutro_tpu.ops import lowering, pallas_kv
from tests.glm_dsa_common import (
    MCFG, MP, PS, TOL, engine, err, sequence, system_of, table_of, want,
)


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine(), num_pages=12)


# -- the one place that says what a token keeps ---------------------------------------

@pytest.mark.parametrize("name,widths,layers", [
    ("tiny-glm-dsa", (128, 24), 4),
    ("glm-5-l5-ep16", (640, 128), 5),
    ("glm-5", (640, 128), 78),
    ("tiny-joyai", (128,), 4),
    ("joyai-llm-flash-ep16", (640,), 40),
    ("tiny-dense", (64, 64), 2),
    ("qwen3-4b", (1024, 1024), 36),
    ("tiny-mellum2", (64, 64), 1),
    ("granite-4.0-h-micro", (512, 512), 4),
])
def test_the_pools_row_widths_by_the_layer_kind(name, widths, layers):
    cfg = MODEL_CONFIGS[name]
    assert cfg.pool_row_widths == widths
    assert cfg.num_pool_layers == layers
    assert cfg.page_width == widths[0]
    assert cfg.index_key_width == (widths[1] if cfg.index_topk else 0)
    assert cfg.pool_has_values is (len(widths) == 2 and not cfg.index_topk)


def test_two_pools_on_one_page_table_and_no_v_pool(runner):
    c = runner.cache
    assert c.k_pages.shape == (4, 12, PS, 128)     # 44 of a row in use
    assert c.ik_pages.shape == (4, 12, PS, 24)
    assert c.v_pages is None and c.k_scale is None and c.wk_pages is None
    info = runner.device_info()
    assert info["latent_layers"] == info["index_layers"] == 4
    assert info["index_key_width"] == 24 and info["index_topk"] == 8
    assert info["index_page_bytes"] == 4 * PS * 24 * 4        # float32 here
    assert info["latent_page_bytes"] == 4 * PS * 44 * 4
    assert info["pool_bytes"] == 12 * 4 * PS * (128 + 24) * 4
    # a model of latent layers with no indexer has no such pool
    plain = kvcache.alloc_cache(MODEL_CONFIGS["tiny-joyai"], engine(), 4)
    assert plain.ik_pages is None and plain.v_pages is None


def test_a_pages_bytes_count_both_pools(runner):
    assert runner._page_bytes_per_device() == 4 * PS * (128 + 24) * 4
    big = types.SimpleNamespace(_sized=kvcache.cache_layout(
        MODEL_CONFIGS["glm-5-l5-ep16"], engine(kv_page_size=64), 2,
    ))
    page = ModelRunner._page_bytes_per_device(big)
    # 5 layers x 64 tokens x (640 + 128) lanes x 2 bytes = 7,680 a token
    assert page == 5 * 64 * (1280 + 256) == 491_520
    for name, want_bytes in (
        ("joyai-llm-flash-ep16", 40 * 64 * 1280),           # as before
        ("qwen3-4b", 2 * 36 * 64 * 1024 * 2),
    ):
        other = types.SimpleNamespace(_sized=kvcache.cache_layout(
            MODEL_CONFIGS[name], engine(kv_page_size=64), 2,
        ))
        assert ModelRunner._page_bytes_per_device(other) == want_bytes


def test_pages_that_fit_divides_what_is_left_by_both_pools_bytes():
    ecfg = engine(kv_page_size=64, max_pages_per_seq=4)
    stats = {"bytes_limit": 1_000_000_000, "bytes_in_use": 600_000_000}
    dev = types.SimpleNamespace(memory_stats=lambda: stats, device_kind="fake")
    fake = types.SimpleNamespace(
        mcfg=MODEL_CONFIGS["glm-5-l5-ep16"], ecfg=ecfg, mesh=None,
        params={}, _margin_pages=0, window_span=0, n_devices=1,
        _sized=kvcache.cache_layout(MODEL_CONFIGS["glm-5-l5-ep16"], ecfg, 2),
    )
    fake._page_bytes_per_device = types.MethodType(
        ModelRunner._page_bytes_per_device, fake)
    real = jax.devices
    jax.devices = lambda *a: [dev]
    try:
        fit, win = ModelRunner._pages_that_fit(fake, 10_000, 0)
    finally:
        jax.devices = real
    assert win == 0 and fit == 200_000_000 // 491_520


# -- the write ----------------------------------------------------------------------

def test_write_kv_lands_the_index_keys_that_ride_in_vs_place():
    cache = kvcache.alloc_cache(MCFG, engine(), 6, dtype=jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 5, 128))
    keys = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 5, 24))
    table = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    out = kvcache.write_kv(
        cache, MixedChunk(k=rows), keys, table,
        jnp.asarray([6, 0], jnp.int32), jnp.asarray([5, 3], jnp.int32),
    )
    assert out.v_pages is None
    for pool, new in ((np.asarray(out.k_pages), rows),
                      (np.asarray(out.ik_pages), keys)):
        # row 0: positions 6..10 -> page 1 slots 6, 7 then page 2 slots 0..2
        assert np.array_equal(pool[:, 1, 6:8], np.asarray(new[:, 0, :2]))
        assert np.array_equal(pool[:, 2, :3], np.asarray(new[:, 0, 2:]))
        # row 1: three valid tokens at page 3; its padding went to page 0
        assert np.array_equal(pool[:, 3, :3], np.asarray(new[:, 1, :3]))
        assert not pool[:, 3, 3:].any() and not pool[:, 4:].any()


def test_the_benchmarks_k_v_pair_writes_the_index_keys(runner):
    """``perfbench/sut.py`` hands ``write_kv`` the ``(k, v)`` of
    ``_trunk_decode`` unopened: ``v`` is the index keys, and a later
    step's selection reads them from the pool (the logits are the
    reference's only if they landed)."""
    ids = sequence(3, 30)
    got = system_of(runner).logits_through_cache(ids, 20, 10)
    assert err(got, want(runner.params, ids, range(19, 30))) < TOL
    logits, _, (k, v) = runner._trunk_decode(
        runner.params, runner.cache, jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([[3]]), jnp.asarray([3]),
        jnp.asarray(table_of(1)[None], jnp.int32),
    )
    assert k.k.shape == (4, 1, 1, 128) and v.shape == (4, 1, 1, 24)


@pytest.mark.parametrize("starts,valids,tb", [
    ([0, 8, 3], [16, 16, 5], 16),     # aligned, offset, ragged
    ([7, 41, 0], [16, 7, 0], 16),     # page-crossing, the table's end, empty
    ([6, 0, 13], [1, 1, 1], 1),       # a decode step's one row
])
def test_the_in_place_write_at_the_index_keys_width_interpreted(
    starts, valids, tb
):
    """The one-pool write kernel's second call: a pool 128 lanes wide
    (the cell's index keys) beside the 640 of the latent rows."""
    rng = np.random.default_rng(tb)
    L, NP, B, W = 2, 20, 3, 128
    pool = jnp.asarray(rng.standard_normal((L, NP, PS, W)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, NP))[: B * 6].reshape(B, 6), jnp.int32)
    rows = jnp.asarray(rng.standard_normal((L, B, tb, W)), jnp.float32)
    start, valid = jnp.asarray(starts, jnp.int32), jnp.asarray(valids, jnp.int32)
    flat = kvcache._flat_slots(table, start, valid, tb, PS)
    ref = kvcache._scatter_rows(pool, flat, rows)
    got = pallas_kv.row_write_pallas(
        pool.copy(), rows, table, start, valid, interpret=True)
    # counted a trace (jit caches one a shape): interpreted, none lowered
    assert lowering.snapshot()["kv_write"]["interpreted"] >= 1
    np.testing.assert_array_equal(np.asarray(got)[:, 1:], np.asarray(ref)[:, 1:])


def test_under_use_pallas_both_pools_go_through_the_kernel(monkeypatch):
    calls = []
    real = pallas_kv.row_write_pallas

    def spy(pool, *a, **kw):
        calls.append(pool.shape[-1])
        return real(pool, *a, interpret=True, **kw)

    monkeypatch.setattr(pallas_kv, "row_write_pallas", spy)
    cache = kvcache.alloc_cache(MCFG, engine(), 6, dtype=jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (4, 1, 3, 128))
    keys = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 3, 24))
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    out = kvcache.write_kv(
        cache, MixedChunk(k=rows), keys, table, jnp.asarray([7], jnp.int32),
        jnp.asarray([3], jnp.int32), use_pallas=True,
    )
    assert calls == [128, 24]
    # (the kernel's pools were donated: a cache of its own for the scatter)
    flat = kvcache.write_kv(
        kvcache.alloc_cache(MCFG, engine(), 6, dtype=jnp.float32),
        MixedChunk(k=rows), keys, table, jnp.asarray([7], jnp.int32),
        jnp.asarray([3], jnp.int32),
    )
    for a, b in ((out.k_pages, flat.k_pages), (out.ik_pages, flat.ik_pages)):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:])


# -- what refuses, by name -----------------------------------------------------------------

def test_int8_kv_a_mesh_and_weight_quantisation_are_refused():
    with pytest.raises(NotImplementedError, match="latent row a token.*kv_quantize"):
        kvcache.alloc_cache(MCFG, engine(kv_quantize="int8"), 6)
    whole = dataclasses.replace(MCFG, name="tiny-glm-dsa: whole",
                                moe_experts_held=0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(NotImplementedError, match="latent row a token"):
        ModelRunner(whole, engine(), mesh=mesh)
    with pytest.raises(NotImplementedError, match="several kinds"):
        ModelRunner(MCFG, engine(quantize="int8"), num_pages=6)


def test_an_indexer_without_its_widths_is_refused():
    from sutro_tpu.models import transformer

    for bad in (dict(index_n_heads=0), dict(index_head_dim=4)):
        cfg = dataclasses.replace(MCFG, name="tiny-glm-dsa: bad", **bad)
        with pytest.raises(ValueError, match="indexer"):
            transformer._check_mixed(cfg)


def test_the_tiers_payload_refuses_the_pools_pages(runner):
    with pytest.raises(ValueError, match="latent pool"):
        runner.read_pages([1, 2])
    with pytest.raises(ValueError, match="latent pool"):
        runner.write_pages([1], {"k": np.zeros((4, 1, PS, 128)),
                                 "v": np.zeros((4, 1, PS, 128))})


# -- what falls back, and counts -------------------------------------------------------------

def _fallback(reason):
    series = telemetry.REGISTRY.collect().get(
        "sutro_state_fallback_prefill_tokens_total", {}
    ).get("series", {})
    return series.get(reason, 0.0)


def test_a_shared_prefix_and_a_tier_fall_back_under_the_latent_pools_reasons(
    runner
):
    from sutro_tpu.engine.prefixstore import PrefixStore

    telemetry.set_enabled(True)
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    head = "a system prompt that is longer than two pages of eight. "
    prompts = [head + tail for tail in ("one", "two", "three")]
    before = _fallback("prefix_on_latent_pool")
    tier = types.SimpleNamespace(page_size=PS)
    b = ContinuousBatcher(
        runner, stop_ids=[], prefix_store=PrefixStore(PS), kv_tier=tier)
    assert b._prefix_store is None
    assert b._layout.refuses("share") == "prefix_on_latent_pool"
    assert b._kv_tier is None and b._tier_refused and not b._can_hibernate
    out = {}
    reqs = [GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                       max_new_tokens=4, temperature=0.0)
            for i, p in enumerate(prompts)]
    b.run(reqs, on_result=lambda r: out.__setitem__(r.row_id, r))
    assert sorted(out) == [0, 1, 2]
    shared = (len(tok.encode(head)) // PS) * PS
    assert _fallback("prefix_on_latent_pool") - before == 2 * shared
    hib = _fallback("hibernate_on_latent_pool")
    b.slots[0] = types.SimpleNamespace(pos=21)
    assert b._hibernate_slot(0) is False
    assert _fallback("hibernate_on_latent_pool") - hib == 21


def test_the_selected_and_context_rows_are_host_arithmetic(runner):
    telemetry.set_enabled(True)

    def rows(kind):
        return telemetry.REGISTRY.collect().get(
            "sutro_sparse_attention_rows_total", {}
        ).get("series", {}).get(kind, 0.0)

    c0, s0, f0 = rows("context"), rows("selected"), rows("fetched")
    # rows of 20 and 5 tokens (two padding rows), 3 steps: contexts of
    # 21, 22, 23 and 6, 7, 8; at most 8 of each are read, and in XLA
    # (``use_pallas`` off) those are the rows fetched, by position
    runner._count_latent("absorbed", np.array([20, 5, 0, 0]), 3)
    assert rows("context") - c0 == 21 + 22 + 23 + 6 + 7 + 8
    assert rows("selected") - s0 == 3 * 8 + 6 + 7 + 8
    assert rows("fetched") - f0 == 3 * 8 + 6 + 7 + 8
    assert lowering.sparse_attention_counts().keys() == {"gathered", "masked"}
    # a selecting call of the paged kernel is a FORM of that kernel's
    # count (by heads), no third way of applying a selection
    assert all(
        key.startswith("paged_decode@") and key.endswith(" select=keep")
        for key in lowering.paged_decode_forms()
    )


@pytest.mark.parametrize("use_pallas,past,fetched", [
    # a dense dispatch (5 + 2 <= 8) gathers the rows' whole tables
    (False, [5, 3], 2 * (MP * PS + 1 + MP * PS + 2)),
    # the paged kernel walks a row's pages up to its last token's: one
    # page of 8 for 5 and for 3 tokens, three for 20, beside the
    # pending tokens and the own row
    (True, [5, 3], 2 * (8 + 1 + 8 + 2)),
    # under a selection pages of 8 are the gate's to refuse: XLA
    # fetches the 8 selected rows by position
    (True, [20, 3], 8 + 8 + 4 + 5),
])
def test_the_fetched_rows_follow_the_body_that_attends(
    runner, monkeypatch, use_pallas, past, fetched
):
    telemetry.set_enabled(True)

    def rows(kind):
        return telemetry.REGISTRY.collect()[
            "sutro_sparse_attention_rows_total"]["series"].get(kind, 0.0)

    monkeypatch.setattr(runner, "use_pallas", use_pallas)
    f0, s0 = rows("fetched"), rows("selected")
    runner._count_latent("absorbed", np.array(past + [0, 0]), 2)
    assert rows("fetched") - f0 == fetched
    assert rows("selected") - s0 == sum(
        min(n + s, 8) for n in past for s in (1, 2))
