"""Compile for a described v5e (no chip attached) what the Mellum 2 cell
runs, with the Pallas kernels lowered by Mosaic, and assert the bytes fit
one chip: the fused decode window at the configuration's batch (the
paged kernel of a window layer fetches from its first visible page) and
the one-row prefill at both buckets the traffic meets (flash attention
at a group of 8 query heads a KV head, the in-place K/V write of 4,096
tokens to each kind's pool), beside the weights and the two pools the
configuration asks for. Guards the sizing of
perfbench/configs/mellum2-12b-a2.5b-l8-v5e1 before chip time is spent
(on-chip-measurement guide, section 2.3).

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
    (REPO / "perfbench/configs/mellum2-12b-a2.5b-l8-v5e1.json").read_text()
)


@pytest.fixture(scope="module")
def plan():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import alloc_cache, window_span_pages
    from sutro_tpu.engine.runner import ModelRunner
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

    params = described(jax.eval_shape(
        functools.partial(transformer.init_params, mcfg, dtype=dtype),
        jax.random.PRNGKey(0),
    ))
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    span = window_span_pages(
        mcfg.sliding_window,
        (ecfg.decode_lookahead + 1) * ecfg.decode_multi_step,
        ecfg.kv_page_size,
    )
    cache = described(jax.eval_shape(lambda: alloc_cache(
        mcfg, ecfg, 1 + B * MP, dtype=dtype, window_pages=1 + B * span,
    )))
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        span=span, weights=nbytes(params), pools=nbytes(cache),
        resident=nbytes(params) + nbytes(cache),
    )


def lowered_since(before):
    from sutro_tpu.ops import lowering

    now = lowering.snapshot()
    return {k: now[k]["lowered"] - before[k]["lowered"] for k in now}


def test_sizing_fills_the_chip_and_fits(plan):
    assert plan["weights"] == 2 * CFG["parameters"]
    c = plan["cache"]
    assert plan["span"] == 18
    assert c.k_pages.shape == (2, 4097, 64, 512)
    assert c.wk_pages.shape == (6, 1153, 64, 512)
    assert c.window_page.shape == (4097,)
    # 1.07 GB of full-layer K/V and 0.91 GB of window K/V, where one pool
    # for the eight layers would hold 4.3 GB
    assert 1.9e9 < plan["pools"] < 2.0e9
    assert 8 * 4097 * 64 * 512 * 2 * 2 > 4.29e9
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] < 0.8 * HBM_LIMIT


def test_decode_window_compiles_with_the_kernels_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.snapshot()
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    # the paged kernel once a kind of layer, the write once a pool
    assert got["paged_decode"] == 2 and got["kv_write"] == 2
    mem = compiled.memory_analysis()
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the pools are read where they lie and committed in place; no
    # layer's 793 MB of experts is copied out of the stack
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.alias_size_in_bytes >= plan["pools"]


@pytest.mark.parametrize("T", [2048, 4096])
def test_one_row_prefill_compiles_with_the_kernels_and_fits(
    plan, silent_cache, T
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert ecfg.prefill_batch_size == 1 and T <= ecfg.prefill_chunk
    MP = ecfg.max_pages_per_seq
    before = lowering.snapshot()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((1, T), jnp.int32), arg((1,), jnp.int32), arg((1, MP), jnp.int32),
        arg((1,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    # one trace serves both kinds (the window is an operand); a write a pool
    assert got["flash_prefill"] >= 1 and got["kv_write"] == 2
    mem = compiled.memory_analysis()
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.alias_size_in_bytes >= plan["pools"]
