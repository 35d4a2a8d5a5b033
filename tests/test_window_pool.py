"""K/V a pool a kind (engine/kvcache.py): the window layers' pool, the
page id -> window page map, the host allocator beside the page free list,
admission against both kinds, and the sizes that result."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import (
    WindowPages, alloc_cache, window_span_pages, window_table,
)
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.models.configs import MODEL_CONFIGS

REPO = Path(__file__).resolve().parents[1]
MCFG = MODEL_CONFIGS["tiny-mellum2"]
W = MCFG.sliding_window
PS, MP, B = 4, 16, 4


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=B,
        max_model_len=PS * MP, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=64, seed=3,
        decode_multi_step=4,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def params():
    return ModelRunner(MCFG, engine(), num_pages=2 + MP).params


def make(params, window_pages=None, **kw):
    return ModelRunner(
        MCFG, engine(**kw), params=params, num_pages=1 + B * MP,
        window_pages=window_pages,
    )


def requests(n, new=20, seed=0):
    rng = np.random.default_rng(seed)
    return [
        GenRequest(
            row_id=i, prompt_ids=rng.integers(3, 250, 9 + 3 * i).astype(np.int32),
            max_new_tokens=new, temperature=0.0,
        )
        for i in range(n)
    ]


def run(runner, reqs):
    b = ContinuousBatcher(runner, stop_ids=[])
    out = {}
    b.run(reqs, on_result=lambda r: out.__setitem__(r.row_id, r))
    return b, {i: list(r.token_ids) for i, r in sorted(out.items())}


# -- the host allocator --------------------------------------------------


def test_window_pages_bind_release_and_budget():
    pool = WindowPages(window_pages=6, num_pages=20)
    assert (pool.total, pool.free_count, pool.budget_free) == (5, 5, 5)
    pool.bind([7, 8, 0, 8])
    assert pool.in_use == 2 and pool.of_page[0] == 0
    ids, wpages = pool.delta()
    assert sorted(ids) == [7, 8] and set(wpages) == {1, 2}
    assert pool.delta() is None
    pool.bind([7])                      # bound: keeps its window page
    assert pool.delta() is None
    assert pool.release([7, 9]) == 1 and pool.in_use == 1
    ids, wpages = pool.delta()
    assert list(ids) == [7] and list(wpages) == [0]
    pool.set_budget(8, 3)
    assert pool.budget_free == 2
    pool.release_row([8, 11])
    assert (pool.in_use, pool.budget_free) == (0, 5)
    pool.bind(range(1, 6))
    with pytest.raises(MemoryError):
        pool.bind([6])
    pool.reset()
    assert pool.free_count == 5 and not pool.of_page.any()


def test_span_is_the_window_and_the_tokens_in_flight():
    # 1,024 / 64 = 16 pages, one for a window that lies across pages, one
    # for the 24 tokens of three fused windows of 8: the issue's 18
    assert window_span_pages(1024, 24, 64) == 18
    assert window_span_pages(8, 0, 4) == 3


# -- release: exactly when a page's last position leaves the window -------


def test_a_page_goes_back_exactly_when_its_last_position_leaves_the_window(params):
    r = make(params, window_pages=1 + B * 9)
    pool = r.pools.window
    table = np.zeros((MP,), np.int32)
    table[:] = np.arange(1, 1 + MP)
    ids = np.arange(3, 33, dtype=np.int32)
    r.prefill(ids[:20], table)
    # a prefill binds the pages the window at its end still sees: a query
    # at 20 sees 13..19, pages 3 and 4
    assert sorted(np.nonzero(pool.of_page)[0]) == [4, 5]
    for committed in range(20, 33):
        r.pools.bind_written(table[None], [committed - 1], [1])
        r.pools.release_behind(table[None], [committed])
        held = set(np.nonzero(pool.of_page)[0] - 1)
        first = max(committed - W + 1, 0) // PS
        # page j holds positions 4j..4j+3: held iff a query at
        # ``committed`` or later can see one of them
        assert held == set(range(first, (committed - 1) // PS + 1)), committed
        assert all(4 * j + 3 >= committed - W + 1 for j in held)
    assert pool.released_total == first - 3
    # the device's map agrees with the host's
    r.pools._flush_window()
    assert np.array_equal(np.asarray(r.cache.window_page), pool.of_page)


def test_no_page_a_dispatch_reads_or_writes_is_unbound(params, monkeypatch):
    """Through the scheduler with two fused windows in flight: at every
    dispatch, after its own binds, each live row's table is bound from
    the page of its oldest visible position to the page of the last
    token the dispatch writes, and never holds more than its span. A
    release ahead of the committed length would fail the first."""
    r = make(params, window_pages=1 + B * 9, decode_lookahead=2)
    pool = r.pools.window
    bind_written = r.pools.bind_written
    seen = []

    def bind(page_tables, starts, lens):
        bind_written(page_tables, starts, lens)
        tables = np.asarray(page_tables).reshape(-1, MP)
        for t, s, n in zip(tables, np.ravel(starts), np.ravel(lens)):
            if not t[0] or n <= 0:
                continue
            lo = max(int(s) - W + 1, 0) // PS if s else int(s + n - W + 1) // PS
            lo = max(lo, 0)
            hi = int(s + n - 1) // PS
            need = t[lo : hi + 1]
            assert pool.of_page[need[need > 0]].all(), (s, n)
            seen.append(int((pool.of_page[t[t > 0]] > 0).sum()))

    monkeypatch.setattr(r.pools, "bind_written", bind)
    _, got = run(r, requests(6, new=30))
    assert seen and max(seen) <= r.window_span
    assert pool.released_total > 0
    assert (pool.in_use, pool.budget_free) == (0, pool.total)
    # and the tokens are those of one pool for both kinds
    _, want = run(make(params), requests(6, new=30))
    assert got == want


# -- admission ---------------------------------------------------------------


def test_admission_waits_for_window_pages_as_for_pages(params):
    """A window pool of ONE row's span: rows are admitted one at a time
    though batch rows and full pages are free, and every row finishes."""
    probe = make(params)
    one_row = probe.window_span
    r = make(params, window_pages=1 + one_row)
    live = []
    build = ContinuousBatcher._build_batch

    def spy(self, active):
        live.append(len(active))
        return build(self, active)

    ContinuousBatcher._build_batch = spy
    try:
        b, got = run(r, requests(3, new=12))
    finally:
        ContinuousBatcher._build_batch = build
    assert max(live) == 1 and len(got) == 3
    assert all(len(t) == 12 for t in got.values())
    assert r.pools.window.budget_free == r.pools.window.total
    _, want = run(probe, requests(3, new=12))
    assert got == want


def test_a_row_reserves_its_span_and_a_chunked_one_twice(params):
    r = make(params, window_pages=1 + B * 9)
    assert r.pools.room(2, False) == 1
    assert r.pools.room(PS * MP, False) == r.window_span
    assert r.pools.room(PS * MP, True) == min(MP, 2 * r.window_span)


# -- the trivial setting -------------------------------------------------------


def test_a_runner_given_its_pools_size_maps_every_page_to_itself(params):
    """As ``perfbench/sut.py`` builds its small runner: ``num_pages``
    alone. One table finds both kinds' pages; nothing is bound or
    released; no setting chose it."""
    r = ModelRunner(MCFG, engine(), params=params, num_pages=1 + MP)
    c = r.cache
    assert r.pools.window is None
    assert c.wk_pages.shape == (3, 1 + MP, PS, MCFG.kv_size)
    assert c.k_pages.shape == (1, 1 + MP, PS, MCFG.kv_size)
    table = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]
    assert np.array_equal(np.asarray(window_table(c, table)), np.asarray(table))
    assert r.pools.release_behind(np.asarray(table), [40]) == 0
    info = r.device_info()
    assert info["window_layers"] == 3
    assert info["window_pool_pages"] == info["pool_pages"]
    fields = {f for f in EngineConfig.__dataclass_fields__}
    assert not [f for f in fields if "window_pool" in f or "kv_pool" in f]


def test_a_model_with_one_kind_keeps_one_pool():
    r = ModelRunner(MODEL_CONFIGS["tiny-dense"], engine(), num_pages=9)
    assert r.cache.wk_pages is None and r.cache.window_page is None
    assert r.pools.window is None and r.window_span == 0
    # gpt-oss's alternating windows are masks over one pool
    oss = ModelRunner(MODEL_CONFIGS["tiny-oss"], engine(), num_pages=9)
    assert oss.cache.wk_pages is None and oss.pools.window is None


# -- the sizes ------------------------------------------------------------------


def test_kv_bytes_at_batch_128_of_4096_under_one_pool_and_two():
    """The published widths, 28 layers (21 window, 7 full), bf16, 128
    rows of 4,096: one pool holds every token in every layer; a pool a
    kind holds the window's span in the 21."""
    from perfbench import bytes_and_flops_swa as counts

    mcfg = MODEL_CONFIGS["mellum2-12b-a2.5b"]
    ecfg = EngineConfig(
        kv_page_size=64, max_pages_per_seq=64, max_model_len=4096,
        decode_batch_size=128,
    )
    rows, pages = 128, 64
    span = window_span_pages(1024, 24, 64)

    def pool_bytes(window_pages):
        cache = jax.eval_shape(functools.partial(
            alloc_cache, mcfg, ecfg, 1 + rows * pages, jnp.bfloat16,
            window_pages=window_pages,
        ))
        return sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in (cache.k_pages, cache.v_pages, cache.wk_pages,
                      cache.wv_pages)
        )

    one, two = pool_bytes(None), pool_bytes(1 + rows * span)
    page = 2 * 64 * 512 * 2                       # K and V, a layer
    assert one == 28 * (1 + rows * pages) * page
    assert two == (7 * (1 + rows * pages) + 21 * (1 + rows * span)) * page
    assert 30.0e9 < one < 30.2e9 and 13.8e9 < two < 14.0e9
    # the counts file agrees, a sequence at a time
    cfg = json.loads(
        (REPO / "perfbench/configs/mellum2-12b-a2.5b-l8-v5e1.json").read_text()
    )
    per_token_layer = counts.kv_bytes_per_token_layer(cfg)
    assert per_token_layer == 2048
    assert counts.kv_bytes_per_sequence(cfg, 3600, one_pool=True) == 8 * 3600 * 2048
    assert counts.kv_bytes_per_sequence(cfg, 3600) == (2 * 3600 + 6 * 1024) * 2048
