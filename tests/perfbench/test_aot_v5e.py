"""Compile for a described v5e:2x2 (no chip attached) what the four-chip
cell runs and what its reference check adds, and assert the per-device
bytes fit: the tp=4 decode window at the configuration's batch beside
its weights and worst-case pool, and the plain reference's layer on the
sharded weights. Guards the sizing of perfbench/configs/qwen3-8b-v5e4-tp4
before chip time is spent (on-chip-measurement guide, section 2.3).

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads(
    (REPO / "perfbench/configs/qwen3-8b-v5e4-tp4.json").read_text()
)
HBM_LIMIT = 15.75e9   # what a v5e's allocator offers ("of 15.75G hbm", PR 21)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def silent_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back; keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def plan(topo):
    """Mesh, configs and sharded shapes of the four-chip cell."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS
    from sutro_tpu.parallel.mesh import auto_mesh
    from sutro_tpu.parallel.sharding import cache_shardings, param_shardings

    ecfg = EngineConfig(**CFG["engine"])
    mcfg = MODEL_CONFIGS[CFG["engine_key"]]
    mesh = auto_mesh(ecfg, devices=list(topo.devices))
    dtype = jnp.dtype(ecfg.param_dtype)
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, mcfg, dtype=dtype),
        jax.random.PRNGKey(0),
    )
    shardings = param_shardings(shapes, mesh)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )
    rep = NamedSharding(mesh, P())
    pages = 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
    pool_shape = (mcfg.num_layers, pages, ecfg.kv_page_size,
                  mcfg.num_kv_heads * mcfg.head_dim)
    pool = jax.ShapeDtypeStruct(
        pool_shape, dtype, sharding=cache_shardings(mesh, mcfg.num_kv_heads)
    )

    def per_device_bytes(tree):
        total = 0
        for leaf in jax.tree.leaves(tree):
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard)) * leaf.dtype.itemsize
        return total

    return dict(
        ecfg=ecfg, mcfg=mcfg, mesh=mesh, params=params, pool=pool, rep=rep,
        weights_per_device=per_device_bytes(params),
        pool_per_device=2 * per_device_bytes(pool),
    )


def test_sizing_fills_the_chips_and_fits(plan):
    """Weights and the worst-case pool: over a quarter of a chip (the
    benchmark's floor for a cell that stands for a deployment), under
    the runner's own budget of 80 % of the device limit."""
    resident = plan["weights_per_device"] + plan["pool_per_device"]
    # 16.4 GB over four chips, the 1.2 GB embedding table on each
    assert 4.9e9 < plan["weights_per_device"] < 5.2e9
    assert resident > 0.25 * 16e9
    assert resident < 0.8 * HBM_LIMIT


def test_tp4_decode_window_compiles_and_fits(topo, plan, silent_cache):
    import jax
    import jax.numpy as jnp

    from sutro_tpu.engine.kvcache import KVCache
    from sutro_tpu.engine.runner import ModelRunner

    ecfg, mcfg, mesh, rep = plan["ecfg"], plan["mcfg"], plan["mesh"], plan["rep"]
    # the runner without its weights: only what the jitted method reads
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, mesh
    r.sp = r.pp = 1
    r.ep_mesh = None
    r.use_pallas, r.kernel_mesh = True, mesh
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    cache = KVCache(k_pages=plan["pool"], v_pages=plan["pool"])
    traced = ModelRunner._decode_multi_jit.trace(
        r, plan["params"], cache, arg((B,), jnp.int32), arg((B,), jnp.int32),
        arg((B, MP), jnp.int32), arg((2,), jnp.uint32),
        arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # the Pallas kernels are in
    assert "all-reduce" in text               # and the tp collectives
    resident = plan["weights_per_device"] + plan["pool_per_device"]
    assert resident + mem.temp_size_in_bytes < HBM_LIMIT, (
        resident, mem.temp_size_in_bytes
    )


def test_reference_layer_compiles_on_the_sharded_weights(topo, plan, silent_cache):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import qwen3_dense

    dims = tuple(sorted(qwen3_dense.dims_of(CFG).items()))
    h = jax.ShapeDtypeStruct((200, CFG["hidden_size"]), jnp.float32,
                             sharding=plan["rep"])
    pos = jax.ShapeDtypeStruct((200,), jnp.int32, sharding=plan["rep"])
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=plan["rep"])
    with jax.default_matmul_precision("highest"):
        traced = qwen3_dense._layer_jit.trace(
            dims, plan["params"]["layers"], idx, h, pos
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    mem = compiled.memory_analysis()
    # one layer up-cast to float32, a quarter of it a chip: well under 1 GB
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
