"""ONE description of a model's pools (``engine/kvcache.py``
``cache_layout``): for every preset, the pools ``alloc_cache`` returns
are the description's arrays, a page's and a slot's bytes are the arrays'
own, the three support answers are the reason labels the fallback counter
documents, and what a layout cannot hold is refused by name, word for
word. Shapes only (``jax.eval_shape``): nothing is
allocated and nothing compiles."""

import dataclasses
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sutro_tpu
from sutro_tpu.engine import kvcache
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import (
    ONE_POOL, PAGE, SLOT, WINDOW_PAGE, RowPools, alloc_cache, cache_layout,
    state_bytes_per_slot,
)
from sutro_tpu.models.configs import MODEL_CONFIGS

NAMES = sorted(MODEL_CONFIGS)
NP, NPW = 41, 13


def engine(**kw):
    base = dict(kv_page_size=16, max_pages_per_seq=8, decode_batch_size=4,
                max_model_len=128, param_dtype="bfloat16",
                activation_dtype="bfloat16")
    base.update(kw)
    return EngineConfig(**base)


def takes_int8(m) -> bool:
    return not (m.num_latent_layers or m.num_window_layers
                or m.state_kind == "kda")


def settings(m):
    """(engine overrides, window_pages) a preset's layout is built at."""
    out = [({}, None)]
    if takes_int8(m):
        out.append((dict(kv_quantize="int8"), None))
    if m.num_window_layers:
        out.append(({}, NPW))
    return out


def built(name, kw, window_pages):
    m, e = MODEL_CONFIGS[name], engine(**kw)
    layout = cache_layout(m, e, NP, jnp.bfloat16, None, window_pages)
    cache = jax.eval_shape(
        lambda: alloc_cache(m, e, NP, jnp.bfloat16, None, window_pages)
    )
    return m, e, layout, cache


CASES = [
    pytest.param(name, kw, wp, id=f"{name}{'-int8' if kw else ''}"
                 f"{'-window' if wp else ''}")
    for name in NAMES for kw, wp in settings(MODEL_CONFIGS[name])
]


@pytest.mark.parametrize("name, kw, window_pages", CASES)
def test_the_pools_are_the_descriptions_arrays(name, kw, window_pages):
    m, e, layout, cache = built(name, kw, window_pages)
    have = {
        f.name: getattr(cache, f.name) for f in dataclasses.fields(cache)
        if getattr(cache, f.name) is not None
    }
    assert sorted(have) == sorted(a.name for a in layout.arrays)
    for a in layout.arrays:
        assert have[a.name].shape == a.shape, a.name
        assert have[a.name].dtype == a.dtype, a.name
        assert a.shape[a.axis] == {
            PAGE: NP, WINDOW_PAGE: layout.window_pages,
            SLOT: 1 + layout.state_slots,
        }.get(a.index, NP), a.name
    assert cache.num_pages == layout.num_pages == NP
    assert cache.num_window_pages == layout.window_pages
    assert cache.num_state_slots == (
        1 + layout.state_slots if layout.state_slots else 0
    )
    assert layout.binds_window == bool(window_pages)
    assert cache.quantized == bool(kw)


BF, I8, F32, I32 = "bfloat16", "int8", "float32", "int32"
#: the arrays the PARENT's hand-built ``alloc_cache`` (f5407e7) returned
#: at these settings, one preset of each kind: the description is held
#: against them, not against itself
PINNED = [
    ("tiny-dense", {}, None, {
        "k_pages": ((2, NP, 16, 64), BF), "v_pages": ((2, NP, 16, 64), BF),
    }),
    ("tiny-dense", dict(kv_quantize="int8"), None, {
        "k_pages": ((2, NP, 16, 64), I8), "v_pages": ((2, NP, 16, 64), I8),
        "k_scale": ((2, NP, 16), F32), "v_scale": ((2, NP, 16), F32),
    }),
    ("tiny-lfm2", {}, None, {
        "k_pages": ((1, NP, 16, 64), BF), "v_pages": ((1, NP, 16, 64), BF),
        "conv": ((NP, 1280), BF),
    }),
    ("tiny-granite", {}, None, {
        "k_pages": ((2, NP, 16, 64), BF), "v_pages": ((2, NP, 16, 64), BF),
        "ssm": ((5, 5, 16, 256), BF), "ssm_conv": ((5, 4320), BF),
        "state_slot": ((NP,), I32),
    }),
    ("tiny-solar-kda", {}, None, {
        "k_pages": ((2, NP, 16, 64), BF), "v_pages": ((2, NP, 16, 64), BF),
        "ssm": ((4, 5, 16, 64), BF), "ssm_conv": ((5, 2304), BF),
        "state_slot": ((NP,), I32),
    }),
    ("tiny-mellum2", {}, None, {
        "k_pages": ((1, NP, 16, 64), BF), "v_pages": ((1, NP, 16, 64), BF),
        "wk_pages": ((3, NP, 16, 64), BF), "wv_pages": ((3, NP, 16, 64), BF),
        "window_page": ((NP,), I32),
    }),
    ("tiny-mellum2", {}, NPW, {
        "k_pages": ((1, NP, 16, 64), BF), "v_pages": ((1, NP, 16, 64), BF),
        "wk_pages": ((3, NPW, 16, 64), BF), "wv_pages": ((3, NPW, 16, 64), BF),
        "window_page": ((NP,), I32),
    }),
    ("tiny-joyai", {}, None, {"k_pages": ((4, NP, 16, 128), BF)}),
    ("tiny-glm-dsa", {}, None, {
        "k_pages": ((4, NP, 16, 128), BF), "ik_pages": ((4, NP, 16, 24), BF),
    }),
    ("tiny-sdar", {}, None, {
        "k_pages": ((3, NP, 16, 64), BF), "v_pages": ((3, NP, 16, 64), BF),
    }),
]


@pytest.mark.parametrize(
    "name, kw, window_pages, want", PINNED,
    ids=[f"{n}{'-int8' if kw else ''}{'-window' if wp else ''}"
         for n, kw, wp, _ in PINNED],
)
def test_the_arrays_are_the_parents(name, kw, window_pages, want):
    layout = cache_layout(
        MODEL_CONFIGS[name], engine(**kw), NP, jnp.bfloat16, None,
        window_pages,
    )
    assert {
        a.name: (a.shape, jnp.dtype(a.dtype).name) for a in layout.arrays
    } == want
    # the map of the trivial setting is born the identity, a bound one 0s
    for a in layout.arrays:
        assert a.identity == (a.name == "window_page" and not window_pages)


@pytest.mark.parametrize("name, kw, window_pages", CASES)
def test_a_pages_and_a_slots_bytes_are_the_arrays_own(name, kw, window_pages):
    m, e, layout, cache = built(name, kw, window_pages)

    def nbytes(x):
        return int(np.prod(x.shape)) * x.dtype.itemsize

    for index, entries, got in (
        (PAGE, NP, layout.page_bytes),
        (WINDOW_PAGE, layout.window_pages, layout.window_page_bytes),
        (SLOT, 1 + layout.state_slots, layout.slot_bytes),
    ):
        total = sum(
            nbytes(getattr(cache, a.name)) for a in layout.arrays
            if a.index == index
        )
        assert got * entries == total, index
    assert state_bytes_per_slot(m, e) == layout.slot_bytes
    # the margin's rows: one layer's page of the K pool
    k = cache.k_pages
    assert layout.margin_row_bytes == nbytes(k) // (k.shape[0] * NP)
    # the padding lanes of a latent row are not in use
    used = layout.entry_bytes("k_pages", used=True)
    if m.num_latent_layers:
        assert used * m.page_width == (
            layout.entry_bytes("k_pages") * m.latent_width
        )
    else:
        assert used == layout.entry_bytes("k_pages")
    assert layout.entry_bytes("no such array") == 0


def test_a_sharded_pools_page_is_a_shards():
    """Under a mesh the K/V pools carry the sharding they are handed and
    everything else replicates: a page on ONE device is a shard of K and
    V and the whole of the scales."""
    from sutro_tpu.parallel.sharding import cache_shardings

    if jax.device_count() < 2:
        pytest.skip("needs two host devices")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    m = MODEL_CONFIGS["tiny-dense"]
    sharding = cache_shardings(mesh, m.num_kv_heads)
    one = cache_layout(m, engine(), NP, jnp.bfloat16)
    two = cache_layout(m, engine(), NP, jnp.bfloat16, sharding)
    assert [a.name for a in two.arrays if a.sharded] == ["k_pages", "v_pages"]
    assert two.page_bytes * 2 == one.page_bytes
    assert two.margin_row_bytes * 2 == one.margin_row_bytes
    q = cache_layout(m, engine(kv_quantize="int8"), NP, jnp.bfloat16, sharding)
    L, PS, KD = m.num_layers, 16, m.page_width
    assert q.page_bytes == 2 * (L * PS * KD // 2 + L * PS * 4)
    cache = two.alloc()                       # tiny: 2 x 41 x 16 x 64
    assert cache.k_pages.sharding == cache.v_pages.sharding == sharding
    lfm = cache_layout(MODEL_CONFIGS["tiny-lfm2"], engine(), NP,
                       jnp.bfloat16, sharding)
    assert not lfm.array("conv").sharded


#: preset, window pages -> (share, tiers, native): the reason a family's
#: fallback is counted under (None: supported)
SUPPORT = {
    ("tiny-dense", None): (None, None, None),
    ("tiny-lfm2", None): (None, None, None),
    ("tiny-granite", None): (
        "prefix_without_state_snapshot", "hibernate_without_slot_state", None),
    ("tiny-solar-kda", None): (
        "prefix_without_state_snapshot", "hibernate_without_slot_state", None),
    # the trivial window pool: the store survives, the tiers do not
    ("tiny-mellum2", None): (None, "hibernate_without_window_pages", None),
    ("tiny-mellum2", NPW): (
        "prefix_without_window_pages", "hibernate_without_window_pages", None),
    ("tiny-joyai", None): (
        "prefix_on_latent_pool", "hibernate_on_latent_pool", None),
    ("tiny-glm-dsa", None): (
        "prefix_on_latent_pool", "hibernate_on_latent_pool", None),
    ("tiny-sdar", None): (
        "prefix_on_block_model", "hibernate_on_block_model", "native"),
}


@pytest.mark.parametrize("name, window_pages", sorted(
    SUPPORT, key=lambda k: (k[0], k[1] or 0)
))
def test_what_the_pages_support_by_kind_of_model(
    name, window_pages
):
    layout = cache_layout(
        MODEL_CONFIGS[name], engine(), NP, jnp.bfloat16, None, window_pages
    )
    share, tiers, native = SUPPORT[name, window_pages]
    assert layout.refuses("share") == share
    assert layout.refuses("tiers") == tiers
    assert (layout.refuses("native") is not None) == (native is not None)
    # the tier verbs of the runner ask the same description
    assert (layout.refuses("read_pages") is None) == (
        tiers in (None, "hibernate_without_slot_state",
                  "hibernate_on_block_model")
    )
    assert (layout.refuses("write_pages") is None) == (
        tiers in (None, "hibernate_on_block_model")
    )
    assert layout.block_length == MODEL_CONFIGS[name].block_length
    assert layout.has_state == (name in (
        "tiny-lfm2", "tiny-granite", "tiny-solar-kda"))


def test_asking_for_bytes_refuses_nothing():
    """A byte query is arithmetic: it answers for a layout that cannot
    be built (``sutro engine info`` with int8 K/V set for a ``kda``
    model printed a number at the parent, and still does)."""
    from click.testing import CliRunner

    from sutro_tpu import cli

    m, e = MODEL_CONFIGS["tiny-solar-kda"], engine(kv_quantize="int8")
    with pytest.raises(NotImplementedError):
        cache_layout(m, e, NP)
    assert state_bytes_per_slot(m, e) == state_bytes_per_slot(m, engine())
    assert state_bytes_per_slot(m, e) == 2 * (4 * 16 * 64 + 2304)
    assert state_bytes_per_slot(MODEL_CONFIGS["tiny-dense"], e) == 0
    b = kvcache.pool_bytes(MODEL_CONFIGS["tiny-lfm2"], engine())
    assert b.entry_bytes("conv") == 1280 * 2
    assert b.page_bytes == 2 * 16 * 64 * 2 + 1280 * 2
    out = CliRunner().invoke(
        cli.cli, ["engine", "info", "--model", "tiny-lfm2"]
    )
    assert out.exit_code == 0, out.output
    # one attention layer's K and V of 64, bf16; five conv layers' state
    assert "kv_bytes_per_token=256 state_bytes_per_page=2560" in out.output


def test_every_reason_is_a_label_the_counter_documents():
    """None renamed, none added: the eight reasons are the ones
    ``OBSERVABILITY.md`` and the telemetry module name."""
    text = Path(sutro_tpu.__file__).parent.parent.joinpath(
        "OBSERVABILITY.md").read_text()
    said = {r for k in SUPPORT for r in SUPPORT[k][:2] if r}
    assert len(said) == 8
    for reason in said:
        assert reason in text, reason


def test_a_runner_without_a_description_is_one_pool_that_supports_all():
    pools = RowPools(ONE_POOL)
    assert pools.slots is None and pools.window is None
    assert all(
        ONE_POOL.refuses(q) is None
        for q in ("share", "tiers", "native", "read_pages", "write_pages")
    )
    assert ONE_POOL.block_length == 1 and not ONE_POOL.has_state
    pools.reset()
    assert pools.room(100, True, lambda: True) == 0
    table = np.arange(1, 9)
    pools.bind(table, table[:3], 0)
    assert not pools.slide(table[None], np.array([40]), [0])
    assert pools.release_behind(table[None], [40]) == 0
    pools.release(table[:3])


def test_a_rows_needs_of_each_pool_in_admissions_order():
    """State slot first, then the window budget; a wait for a slot is
    counted only while the batch has a free slot."""
    from sutro_tpu import telemetry

    told = []
    g = cache_layout(MODEL_CONFIGS["tiny-granite"], engine(), 3)
    pools = RowPools(g, told.append, None)
    assert pools.slots.total == g.state_slots == 2
    table = np.array([1, 2, 0, 0])
    room = pools.room(20, False)
    assert room == 0
    pools.bind(table, table[:2], room)
    assert pools.slots.in_use == 1 and not told    # told with the prefill
    pools.bind_fresh(table[None], [0])
    assert told == [[(1, 1)]]
    pools.bind(np.array([2, 0, 0, 0]), [2], 0)

    def waits():
        series = telemetry.REGISTRY.collect().get(
            "sutro_state_slot_waits_total", {}
        ).get("series", {})
        return sum(series.values())

    before = waits()
    assert pools.room(20, False, lambda: False) is None
    assert waits() == before
    assert pools.room(20, False, lambda: True) is None
    if telemetry.ENABLED:
        assert waits() == before + 1
    pools.release([1])
    assert pools.room(20, False) == 0
    pools.reset()
    assert pools.slots.in_use == 0

    m = MODEL_CONFIGS["tiny-mellum2"]
    w = cache_layout(m, engine(), NP, jnp.bfloat16, None, NPW)
    changed = []
    pools = RowPools(w, None, lambda ids, wp: changed.append(len(ids)))
    span = w.window_span
    assert pools.room(2, False) == 1
    assert pools.room(16 * 8, False) == span
    assert pools.room(16 * 8, True) == min(8, 2 * span)
    table = np.arange(1, 9)
    rooms = []
    while (room := pools.room(16 * 8, False)) is not None:
        pools.bind(table + 8 * len(rooms), table + 8 * len(rooms), room)
        rooms.append(room)
    assert rooms and sum(rooms) <= pools.window.total < sum(rooms) + span
    pools.bind_written(table[None], [0], [40])
    assert changed and pools.window.in_use == len(
        range(max(40 - m.sliding_window + 1, 0) // 16, 39 // 16 + 1)
    )

    def held():
        series = telemetry.REGISTRY.collect().get(
            "sutro_kv_window_pages_held_total", {}
        ).get("series", {})
        return sum(series.values())

    # the counters move where the CALLER counts (the scheduler's latched
    # flag), not by the live switch
    before = held()
    assert pools.slide(table[None], np.array([40]), [0])
    assert held() == before
    assert pools.slide(
        table[None], np.array([40 + m.sliding_window]), [0], count=True
    )
    if telemetry.ENABLED:
        assert held() > before
    pools.release(table)
    assert pools.window.budget_free == pools.window.total - sum(rooms[1:])
    pools.reset()
    assert pools.window.in_use == 0 and pools.window.budget_free == (
        pools.window.total
    )


REFUSED = [
    ("tiny-joyai", dict(kv_quantize="int8"), False, NotImplementedError,
     "tiny-joyai keeps a latent row a token: the latent pool has no int8 "
     "scale pools (kv_quantize)"),
    ("tiny-joyai", {}, True, NotImplementedError,
     "tiny-joyai keeps a latent row a token: every head reads the whole "
     "row, so the latent pool does not shard over a mesh"),
    ("tiny-solar-kda", dict(kv_quantize="int8"), False, NotImplementedError,
     "tiny-solar-kda keeps a delta-rule state a slot: int8 K/V beside it "
     "(kv_quantize) is not built"),
    ("tiny-solar-kda", {}, True, NotImplementedError,
     "tiny-solar-kda keeps a delta-rule state a slot: the slot pool under "
     "a mesh is not built"),
    ("tiny-mellum2", dict(kv_quantize="int8"), False, NotImplementedError,
     "tiny-mellum2 keeps K/V a pool a kind: the window pool has no int8 "
     "scale pools (kv_quantize)"),
    ("tiny-dense", dict(kv_quantize="fp4"), False, ValueError,
     "Unknown kv_quantize mode 'fp4' (only 'int8')"),
]


@pytest.mark.parametrize(
    "name, kw, meshed, error, words", REFUSED,
    ids=[f"{r[0]}-{'mesh' if r[2] else r[1]['kv_quantize']}" for r in REFUSED],
)
def test_what_a_layout_cannot_hold_is_refused_by_name(
    name, kw, meshed, error, words
):
    sharding = None
    if meshed:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, None, None, "model")
        )
    with pytest.raises(error, match=re.escape(words)):
        cache_layout(MODEL_CONFIGS[name], engine(**kw), NP, jnp.bfloat16,
                     sharding)
    with pytest.raises(error, match=re.escape(words)):
        alloc_cache(MODEL_CONFIGS[name], engine(**kw), NP, jnp.bfloat16,
                    sharding)


def test_no_lower_layer_imports_the_engine():
    """The kernels', the models' and the parallel layer's modules know no
    engine: the four pure page functions live in ``ops/pages.py`` and
    ``engine/kvcache.py`` re-exports them."""
    root = Path(sutro_tpu.__file__).parent
    up = re.compile(r"^\s*(from|import)\s+\S*engine", re.M)
    for layer in ("ops", "models", "parallel"):
        for path in sorted((root / layer).glob("*.py")):
            assert not up.search(path.read_text()), path
    from sutro_tpu.ops import pages

    for fn in ("first_live_page", "window_span_pages", "gather_pages",
               "gather_kv_layer"):
        assert getattr(kvcache, fn) is getattr(pages, fn)
