"""Compile for a described v5e (no chip attached) what the Nemotron 3
Nano cell runs, and assert the bytes fit one chip: the fused decode
window at the configuration's batch and the one-row prefill at the
traffic's buckets, with the kernels (flash prefill at SIXTEEN query
heads a KV head, the paged decode kernel, the K/V write), beside the
weights (64 held experts a routed block, read by the grouped kernel
though 1,856 is off the 128-lane grid), the paged pool over the TWO
attention blocks and the state pool of a slot a batch row; that neither
program copies the state pool or a block's experts out of their stacks;
and the plain reference's blocks on the served weights. Guards the
sizing of perfbench/configs/nemotron-3-nano-30b-a3b-l14-ep2-v5e1 before
chip time is spent (on-chip-measurement guide, section 2.3).

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

from tests.perfbench.test_aot_granite_v5e import pool_sized_temporaries
from tests.perfbench.test_aot_v5e import HBM_LIMIT, silent_cache  # noqa: F401

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads((
    REPO / "perfbench/configs/nemotron-3-nano-30b-a3b-l14-ep2-v5e1.json"
).read_text())


@pytest.fixture(scope="module")
def plan():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import alloc_cache
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
    pages = 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
    cache = described(jax.eval_shape(
        lambda: alloc_cache(mcfg, ecfg, pages, dtype=dtype)
    ))
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
        resident=nbytes(params) + nbytes(cache), weights=nbytes(params),
        state=nbytes((cache.ssm, cache.ssm_conv)),
        experts=nbytes(params["layers"]["moe"]["we_up_t"]) // 6,
    )


def lowered_since(before):
    from sutro_tpu.ops import lowering

    now = lowering.snapshot()
    return {k: now[k]["lowered"] - before[k]["lowered"] for k in now}


def test_sizing_fills_the_chip_and_fits(plan):
    # every leaf in bf16 but the 3 x 6 x 64 per-head scalars and the
    # 6 x 128 selection biases, in float32
    f32 = 3 * 6 * 64 + 6 * 128
    assert plan["weights"] == 2 * CFG["parameters"] + 2 * f32
    assert CFG["parameters"] == 4_584_903_936
    c = plan["cache"]
    # the pool spans the TWO attention blocks, a page row of 2 x 128
    assert c.k_pages.shape == (2, 8193, 64, 256) and c.conv is None
    # a slot a row of the batch and the garbage slot
    assert c.ssm.shape == (6, 257, 128, 4096)
    assert 1.6e9 < plan["state"] < 1.7e9
    assert 1.0e9 < plan["resident"] - plan["weights"] - plan["state"] < 1.1e9
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] < 0.8 * HBM_LIMIT


def test_decode_window_compiles_with_the_kernels_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.snapshot()
    grouped = lowering.grouped_matmul_counts()
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    assert got["paged_decode"] >= 1 and got["kv_write"] >= 1
    # 1,856 is off the 128 grid: the kernel takes each expert's matrix
    # as ONE block, the first where it lies output-major; no ragged_dot
    now = lowering.grouped_matmul_counts()
    assert now["lowered"] > grouped["lowered"]
    assert now["reference"] == grouped["reference"]
    mem = compiled.memory_analysis()
    print("decode window temp bytes", mem.temp_size_in_bytes,
          "resident", plan["resident"])
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the pools are read where they lie and committed in place (the
    # window's own buffers and the scans' carries are 2.7 GB); no stack
    # of experts is copied: 3.8 GB, which a [L, E, H, 1856] stack was,
    # whole, before every product (PERF.md section 6, PR 40)
    assert mem.temp_size_in_bytes < 3.0e9 < 6 * plan["experts"]
    assert mem.alias_size_in_bytes >= plan["resident"] - plan["weights"]
    assert pool_sized_temporaries(compiled, plan) == []


@pytest.mark.parametrize("T", [128, 1024])
def test_one_row_prefill_compiles_with_flash_at_sixteen_heads_a_kv_head(
    plan, silent_cache, T
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert ecfg.prefill_batch_size == 1 and T <= ecfg.prefill_chunk
    assert plan["mcfg"].num_heads // plan["mcfg"].num_kv_heads == 16
    MP = ecfg.max_pages_per_seq
    before = lowering.snapshot()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((1, T), jnp.int32), arg((1,), jnp.int32), arg((1, MP), jnp.int32),
        arg((1,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    now = lowering.snapshot()
    assert got["flash_prefill"] >= 1 and got["kv_write"] >= 1
    assert now["flash_prefill"]["reference"] == before["flash_prefill"]["reference"]
    mem = compiled.memory_analysis()
    print("prefill", T, "temp bytes", mem.temp_size_in_bytes)
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    assert mem.temp_size_in_bytes < plan["experts"]
    assert mem.alias_size_in_bytes >= plan["resident"] - plan["weights"]
    assert pool_sized_temporaries(compiled, plan) == []


@pytest.mark.parametrize("symbol", ["M", "*", "E"])
def test_reference_block_compiles_on_the_served_weights(
    plan, silent_cache, symbol
):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import nemotron_h_moe

    dims = tuple(sorted(nemotron_h_moe.dims_of(CFG).items()))
    arg = plan["arg"]
    h = arg((200, CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        traced = nemotron_h_moe._block_jit.trace(
            dims, symbol, plan["params"]["layers"], arg((), jnp.int32), h,
            arg((200,), jnp.int32), None, True,
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # a block's matrices up-cast to float32 an expert at a time
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
