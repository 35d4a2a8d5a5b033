"""Compile for a described v5e (no chip attached) what the Laguna S 2.1
cell runs, with the Pallas kernels lowered by Mosaic, and assert the
bytes fit one chip: the fused decode window at the configuration's batch
(the paged kernel at 48 heads over the full pool and at 72 heads over
the window pool, 6 and 9 query heads a KV head, in ONE program; the
grouped kernel over 32 held experts of 1,024), the one-row prefill at
the largest bucket the mixed traffic meets ([1, 8192]: flash prefill at
groups of 6 and 9, the in-place K/V write to each kind's pool in runs,
the routed layers a tile of 4,096 tokens at a time), beside 6.4 GB of
weights
and the two pools ``ModelRunner._pages_that_fit`` hands out. Guards the
sizing of perfbench/configs/laguna-s-2.1-l9-ep8-v5e1 before chip time
is spent (on-chip-measurement guide, section 2.3).

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

from tests.perfbench.test_aot_v5e import HBM_LIMIT, silent_cache  # noqa: F401

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads(
    (REPO / "perfbench/configs/laguna-s-2.1-l9-ep8-v5e1.json").read_text()
)
#: what a v5e reports as its limit (15.75 GiB) to ``_pages_that_fit``
DEVICE_LIMIT = 15.75 * 2**30


@pytest.fixture(scope="module")
def plan():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import alloc_cache, window_span_pages
    from sutro_tpu.engine.runner import (
        HBM_RESERVE_FRACTION, ModelRunner, _pool_margin_pages,
    )
    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1),
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    ecfg = EngineConfig(**CFG["engine"])
    mcfg = MODEL_CONFIGS[CFG["engine_key"]]
    dtype = jnp.dtype(ecfg.param_dtype)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def described(tree):
        return jax.tree.map(lambda s: arg(s.shape, s.dtype), tree)

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    params = described(jax.eval_shape(
        functools.partial(transformer.init_params, mcfg, dtype=dtype),
        jax.random.PRNGKey(0),
    ))
    B, MP, PS = ecfg.decode_batch_size, ecfg.max_pages_per_seq, ecfg.kv_page_size
    span = window_span_pages(
        mcfg.sliding_window,
        (ecfg.decode_lookahead + 1) * ecfg.decode_multi_step, PS,
    )
    # the pools ``_pages_that_fit`` hands out: the window pool whole (a
    # span a row of the batch), the full pool what the limit leaves
    # beside the weights and the reserve, less the kernel path's margin
    row = PS * mcfg.page_width * 2 * 2          # K and V of a page a layer
    wpage, page = mcfg.num_window_layers * row, mcfg.num_attn_layers * row
    window_pages = 1 + B * span
    avail = (
        DEVICE_LIMIT * (1 - HBM_RESERVE_FRACTION) - nbytes(params)
        - window_pages * wpage
    )
    pages = int(avail // page) - _pool_margin_pages(MP, row // 2)
    cache = described(jax.eval_shape(lambda: alloc_cache(
        mcfg, ecfg, pages, dtype=dtype, window_pages=window_pages,
    )))
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True
    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        span=span, pages=pages, weights=nbytes(params), pools=nbytes(cache),
        resident=nbytes(params) + nbytes(cache),
    )


def lowered_since(before):
    from sutro_tpu.ops import lowering

    now = lowering.snapshot()
    return {k: now[k]["lowered"] - before[k]["lowered"] for k in now}


def test_sizing_fills_the_chip_and_fits(plan):
    assert plan["weights"] == 2 * CFG["parameters"]
    c = plan["cache"]
    assert plan["span"] == 10
    # 3 full layers x 768 KB a page of 64 tokens, 6 window layers x 10
    # pages a row of the batch
    assert c.wk_pages.shape == (6, 1281, 64, 1024)
    assert c.k_pages.shape[0] == 3 and c.k_pages.shape[2:] == (64, 1024)
    # the traffic holds about 3,600 full pages at 128 rows (PERF.md
    # section 6, PR 61): the pool has room for them and is no worst case
    assert 4500 < plan["pages"] < 128 * 128
    assert plan["resident"] > 0.7 * 16e9
    assert plan["resident"] < 0.81 * DEVICE_LIMIT


def test_decode_window_compiles_at_both_head_counts_and_fits(
    plan, silent_cache
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.snapshot()
    heads_before = lowering.kernel_heads_counts()
    grouped = lowering.grouped_matmul_counts()["lowered"]
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    # the paged kernel at least once a kind of layer (layer 0 stands
    # outside the scan of the two periods), the write once a pool
    assert got["paged_decode"] >= 2 and got["kv_write"] == 2
    heads = lowering.kernel_heads_counts()
    for n in (48, 72):
        key = f"paged_decode@{n}"
        assert heads[key]["lowered"] > heads_before.get(key, {}).get(
            "lowered", 0), heads
        assert heads[key]["reference"] == 0
    assert lowering.grouped_matmul_counts()["lowered"] > grouped
    mem = compiled.memory_analysis()
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the pools are read where they lie and committed in place (0.80 GB
    # of temporaries, of which the two kinds' query projections in the
    # layout the scan wants 0.45: no pool, and not the 4.8 GB of held
    # experts)
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.alias_size_in_bytes >= plan["pools"]


@pytest.mark.parametrize("B,T", [(1, 8192)])
def test_prefill_compiles_at_both_head_counts_and_fits(
    plan, silent_cache, B, T
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert T <= ecfg.prefill_chunk
    MP = ecfg.max_pages_per_seq
    before = lowering.snapshot()
    heads_before = lowering.kernel_heads_counts()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B, T), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((B,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    # (the write's runs of 1,024 tokens are one trace however many
    # programs take them: counted where they were traced first)
    assert got["flash_prefill"] >= 2
    assert lowering.snapshot()["kv_write"]["lowered"] >= 2
    assert lowering.snapshot()["kv_write"]["reference"] == 0
    heads = lowering.kernel_heads_counts()
    for n in (48, 72):
        key = f"flash_prefill@{n}"
        assert heads[key]["lowered"] > heads_before.get(key, {}).get(
            "lowered", 0), heads
        assert heads[key]["reference"] == 0
    mem = compiled.memory_analysis()
    # the pools take what the limit leaves beside the weights and the
    # reserve of a fifth (3.38 GB): the largest program's temporaries
    # (2.4 GB at 8,192 tokens, the routed layers taking
    # ``moe_token_tile`` 4,096 tokens at a time; 3.4 GB and no room
    # without the tile) fit it with a quarter to spare
    assert mem.temp_size_in_bytes < 0.75 * 0.2 * DEVICE_LIMIT, (
        mem.temp_size_in_bytes
    )
    assert plan["resident"] + mem.temp_size_in_bytes < DEVICE_LIMIT
    assert mem.alias_size_in_bytes >= plan["pools"]
