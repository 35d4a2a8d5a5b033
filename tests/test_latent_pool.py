"""The latent page pool (engine/kvcache.py): ONE pool whose rows are
``ModelConfig.page_width`` wide and no V pool; a page's bytes and the
pool's size from the layer kind and never from ``num_kv_heads *
head_dim``; the write of a chunk's rows; and each thing that does not
work yet refusing by name, or falling back and counting under a reason
of its own.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine import kvcache
from sutro_tpu.engine.runner import ModelRunner, _pool_margin_pages
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.models.transformer import MixedChunk
from sutro_tpu.ops import lowering
from tests.joyai_common import MCFG, MP, PS, engine, sequence, table_of


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine(), num_pages=12)


# -- the one place that says a page's width ----------------------------------------

@pytest.mark.parametrize("name,width,layers,values", [
    ("tiny-joyai", 128, 4, False),
    ("joyai-llm-flash-ep16", 640, 40, False),
    ("joyai-llm-flash", 640, 40, False),
    ("tiny-dense", 64, 2, True),
    ("qwen3-4b", 1024, 36, True),
    ("tiny-mellum2", 64, 1, True),
    ("tiny-lfm2", 64, 1, True),
    ("tiny-nemotron-h", 64, 2, True),
    ("granite-4.0-h-micro", 512, 4, True),
])
def test_page_width_and_pool_layers_by_the_layer_kind(name, width, layers, values):
    cfg = MODEL_CONFIGS[name]
    assert cfg.page_width == width
    assert cfg.num_pool_layers == layers
    assert cfg.pool_has_values is values
    if values:
        # every other model: what it was before there was a latent kind
        assert width == cfg.num_kv_heads * cfg.head_dim
        assert layers == cfg.num_attn_layers and cfg.num_latent_layers == 0
    else:
        # the latent values and the shared key, padded to whole tiles
        # of 128 lanes (what the device keeps of such a row anyway)
        latent = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        assert cfg.latent_width == latent
        assert width == -(-latent // 128) * 128 and width - latent < 128
        assert width != cfg.num_kv_heads * cfg.head_dim
        assert cfg.num_attn_layers == 0 and cfg.num_kv_layers == layers


def test_one_pool_of_latent_rows_and_no_v_pool(runner):
    c = runner.cache
    assert c.k_pages.shape == (4, 12, PS, 128)    # 48 of a row in use
    assert c.v_pages is None and c.k_scale is None
    assert c.conv is None and c.ssm is None and c.wk_pages is None
    assert c.page_size == PS and c.num_pages == 12
    info = runner.device_info()
    assert info["latent_layers"] == 4 and info["latent_row_width"] == 48
    assert info["latent_row_lanes"] == 128
    assert info["latent_page_bytes"] == 4 * PS * 48 * 4       # float32 here
    assert info["pool_layers"] == 4 and info["pool_pages"] == 12
    # the pool's bytes are the padded rows'
    assert info["pool_bytes"] == 12 * 4 * PS * 128 * 4


def test_a_pages_bytes_are_the_latent_rows_alone(runner):
    assert runner._page_bytes_per_device() == 4 * PS * 128 * 4
    big = types.SimpleNamespace(_sized=kvcache.cache_layout(
        MODEL_CONFIGS["joyai-llm-flash-ep16"], engine(kv_page_size=64), 2,
    ))
    page = ModelRunner._page_bytes_per_device(big)
    # 40 layers x 64 tokens x 640 lanes x 2 bytes: what the device keeps
    # of rows of 576 (46,080 bytes a token in use), where 32 heads of K
    # (192) and V (128) would be 819,200 a token
    assert page == 40 * 64 * 1280 == 64 * 51_200
    dense = types.SimpleNamespace(_sized=kvcache.cache_layout(
        MODEL_CONFIGS["qwen3-4b"], engine(kv_page_size=64), 2,
    ))
    assert ModelRunner._page_bytes_per_device(
        dense
    ) == 2 * 36 * 64 * 1024 * 2                     # K and V, as before
    # the margin's chunk is counted from the same width (the Pallas
    # path's; 0 where the kernels are off)
    assert _pool_margin_pages(64, 64 * 576 * 2) == _pool_margin_pages(64, 73_728)


def test_pages_that_fit_divides_what_is_left_by_a_latent_pages_bytes(runner):
    """A device that reports a limit: the pool is what fits beside the
    weights and the reserve, in pages of the latent width."""
    ecfg = engine(kv_page_size=64, max_pages_per_seq=4)
    stats = {"bytes_limit": 1_000_000_000, "bytes_in_use": 600_000_000}
    dev = types.SimpleNamespace(memory_stats=lambda: stats, device_kind="fake")
    fake = types.SimpleNamespace(
        mcfg=MODEL_CONFIGS["joyai-llm-flash-ep16"], ecfg=ecfg, mesh=None,
        params={}, _margin_pages=0, window_span=0, n_devices=1,
        _sized=kvcache.cache_layout(
            MODEL_CONFIGS["joyai-llm-flash-ep16"], ecfg, 2),
    )
    fake._page_bytes_per_device = types.MethodType(
        ModelRunner._page_bytes_per_device, fake)
    real = jax.devices
    jax.devices = lambda *a: [dev]
    try:
        fit, win = ModelRunner._pages_that_fit(fake, 10_000, 0)
    finally:
        jax.devices = real
    page = 40 * 64 * 1280                  # rows of 640 lanes
    assert win == 0 and fit == (1_000_000_000 - 600_000_000 - 200_000_000) // page


# -- the write ----------------------------------------------------------------------

def test_write_kv_scatters_one_row_a_token_and_pads_to_the_garbage_page(runner):
    cache = kvcache.alloc_cache(MCFG, engine(), 6, dtype=jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 5, 128))
    table = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    out = kvcache.write_kv(
        cache, MixedChunk(k=rows), None, table,
        jnp.asarray([6, 0], jnp.int32), jnp.asarray([5, 3], jnp.int32),
    )
    assert out.v_pages is None
    pool = np.asarray(out.k_pages)
    # row 0: positions 6..10 -> page 1 slots 6, 7 then page 2 slots 0..2
    assert np.array_equal(pool[:, 1, 6:8], np.asarray(rows[:, 0, :2]))
    assert np.array_equal(pool[:, 2, :3], np.asarray(rows[:, 0, 2:]))
    # row 1: three valid tokens at page 3; its padding went to page 0
    assert np.array_equal(pool[:, 3, :3], np.asarray(rows[:, 1, :3]))
    assert not pool[:, 3, 3:].any() and not pool[:, 4:].any()


# -- what refuses, by name -------------------------------------------------------------

def test_int8_kv_is_refused_for_a_latent_pool():
    with pytest.raises(NotImplementedError, match="latent row a token.*kv_quantize"):
        kvcache.alloc_cache(MCFG, engine(kv_quantize="int8"), 6)


def test_a_mesh_is_refused_for_a_latent_pool():
    whole = dataclasses.replace(MCFG, name="tiny-joyai: whole",
                                moe_experts_held=0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(NotImplementedError, match="latent row a token"):
        ModelRunner(whole, engine(), mesh=mesh)
    # and the held share refuses a mesh under its own name
    with pytest.raises(NotImplementedError):
        ModelRunner(MCFG, engine(), mesh=mesh)


def test_weight_quantisation_is_refused_for_layers_of_several_kinds():
    with pytest.raises(NotImplementedError, match="several kinds"):
        ModelRunner(MCFG, engine(quantize="int8"), num_pages=6)


def test_latent_layers_beside_kv_layers_are_refused():
    from sutro_tpu.models import transformer

    mixed = dataclasses.replace(
        MCFG, name="tiny-joyai: mixed",
        layer_types=("mla", "attention", "mla", "mla"),
    )
    with pytest.raises(NotImplementedError, match="pool of another page width"):
        transformer._check_mixed(mixed)
    half = dataclasses.replace(MCFG, name="tiny-joyai: half-split",
                               rope_interleave=False)
    with pytest.raises(NotImplementedError, match="interleaved"):
        transformer._check_mixed(half)


def test_the_tiers_payload_refuses_a_latent_pools_pages(runner):
    with pytest.raises(ValueError, match="latent pool"):
        runner.read_pages([1, 2])
    with pytest.raises(ValueError, match="latent pool"):
        runner.write_pages([1], {"k": np.zeros((4, 1, PS, 128)),
                                 "v": np.zeros((4, 1, PS, 128))})


# -- what falls back, and counts ----------------------------------------------------------

def _fallback(reason):
    series = telemetry.REGISTRY.collect().get(
        "sutro_state_fallback_prefill_tokens_total", {}
    ).get("series", {})
    return series.get(reason, 0.0)


def test_a_shared_prefix_and_the_store_fall_back_to_each_rows_own_prefill(runner):
    from sutro_tpu.engine.prefixstore import PrefixStore

    telemetry.set_enabled(True)
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    head = "a system prompt that is longer than two pages of eight. "
    prompts = [head + tail for tail in ("one", "two", "three")]
    before = _fallback("prefix_on_latent_pool")
    store = PrefixStore(PS)
    b = ContinuousBatcher(runner, stop_ids=[], prefix_store=store)
    assert b._prefix_store is None
    assert b._layout.refuses("share") == "prefix_on_latent_pool"
    out = {}
    reqs = [GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                       max_new_tokens=4, temperature=0.0)
            for i, p in enumerate(prompts)]
    b.run(reqs, on_result=lambda r: out.__setitem__(r.row_id, r))
    assert sorted(out) == [0, 1, 2]
    assert all(len(r.token_ids) == 4 for r in out.values())
    shared = (len(tok.encode(head)) // PS) * PS
    assert _fallback("prefix_on_latent_pool") - before == 2 * shared


def test_a_tier_is_refused_and_hibernation_counts_its_reason(runner):
    tier = types.SimpleNamespace(page_size=PS)
    b = ContinuousBatcher(runner, stop_ids=[], kv_tier=tier)
    assert b._kv_tier is None and b._tier_refused and not b._can_hibernate
    telemetry.set_enabled(True)
    before = _fallback("hibernate_on_latent_pool")
    b.slots[0] = types.SimpleNamespace(pos=21)
    assert b._hibernate_slot(0) is False
    assert _fallback("hibernate_on_latent_pool") - before == 21


def test_the_fetched_and_needed_page_counts_are_the_gathered_tables(runner):
    telemetry.set_enabled(True)
    runner.take_kv_pages()
    past = np.array([20, 9, 0, 0], np.int32)
    tables = np.zeros((4, MP), np.int32)
    runner._count_kv_pages(past, tables, 2, None)
    fetched, needed = runner.take_kv_pages()
    # the gathered path fetches every row's whole table, 4 layers x 2 steps
    assert fetched == 2 * 4 * 4 * MP
    assert needed == pytest.approx(2 * 4 * (20 + 9) / PS)
