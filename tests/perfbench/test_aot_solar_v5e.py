"""Compile for a described v5e (no chip attached) what the Solar Open 2
cell runs, and assert the bytes fit one chip: the fused decode window at
the configuration's batch (the delta-rule state read by
``ssm_state_read``'s body with a head a group and committed in place by
``kda_state_commit``, the paged decode kernel over the two GQA layers,
the grouped kernel over 20 held experts of 1,280) and the one-row
prefill at the traffic's buckets (the chunk form, flash prefill at
eight query heads a KV head), beside the weights, the paged pool over
the TWO attention layers and the state pool of a slot a batch row; that
neither program copies the state pool out of its stack; and the plain
reference's layers on the served weights. Guards the sizing of
perfbench/configs/solar-open2-250b-l8-ep16-v5e1 before chip time is
spent (on-chip-measurement guide, section 2.3).

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
    REPO / "perfbench/configs/solar-open2-250b-l8-ep16-v5e1.json"
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
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True
    # the pool ``ModelRunner._pages_that_fit`` leaves room for: the chip's
    # limit less the weights, the state pool, the fused window's buffers
    # for the uncommitted tokens and the reserve, in pages of both layers
    from sutro_tpu.engine.kvcache import state_bytes_per_slot
    from sutro_tpu.engine.runner import HBM_RESERVE_FRACTION

    weights = 2 * CFG["parameters"]
    state = (1 + ecfg.decode_batch_size) * state_bytes_per_slot(mcfg, ecfg)
    page = 2 * 2 * ecfg.kv_page_size * mcfg.page_width * dtype.itemsize
    pages = int(
        (HBM_LIMIT * (1 - HBM_RESERVE_FRACTION) - weights - state
         - r._window_state_bytes()) // page
    )
    cache = described(jax.eval_shape(
        lambda: alloc_cache(mcfg, ecfg, pages, dtype=dtype)
    ))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        resident=nbytes(params) + nbytes(cache), weights=nbytes(params),
        state=nbytes((cache.ssm, cache.ssm_conv)),
        experts=nbytes(params["layers"]["moe"]["we_up"]) // 8,
    )


def lowered_since(before):
    from sutro_tpu.ops import lowering

    now = lowering.snapshot()
    return {k: now[k]["lowered"] - before[k]["lowered"] for k in now}


def test_sizing_fills_the_chip_and_fits(plan):
    # every leaf in bf16 but dt_bias (6 x 8,192), a_log (6 x 64) and the
    # 8 x 320 selection biases, in float32
    f32 = 6 * 8192 + 6 * 64 + 8 * 320
    assert plan["weights"] == 2 * CFG["parameters"] + 2 * f32
    assert CFG["parameters"] == 3_898_842_752
    c = plan["cache"]
    # the pool spans the TWO attention layers, a page row of 8 x 128
    # about 1,800 pages: 9 a row of the batch, 600 tokens (the traffic's
    # rows hold 460 on average, 1,075 at most)
    assert c.k_pages.shape[0] == 2 and c.k_pages.shape[2:] == (64, 1024)
    assert 1700 < c.k_pages.shape[1] < 1950 and c.conv is None
    # a slot a row of the batch and the garbage slot: [dk, heads x dv]
    assert c.ssm.shape == (6, 193, 128, 8192)
    assert c.ssm_conv.shape == (193, 6 * 3 * 24576)
    assert 2.5e9 < plan["state"] < 2.7e9
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] < 0.85 * HBM_LIMIT


def test_decode_window_compiles_with_the_kernels_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.snapshot()
    grouped = lowering.grouped_matmul_counts()
    read = lowering.kda_state_read_counts()
    commit = lowering.kda_state_commit_counts()
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    assert got["paged_decode"] >= 1 and got["kv_write"] >= 1
    now = lowering.grouped_matmul_counts()
    assert now["lowered"] > grouped["lowered"]
    assert now["reference"] == grouped["reference"]
    # the state's two products and its commit took the kernels
    for was, name in ((read, "kda_state_read"), (commit, "kda_state_commit")):
        now = getattr(lowering, name + "_counts")()
        assert now["lowered"] > was["lowered"], name
        assert now["reference"] == was["reference"], name
    mem = compiled.memory_analysis()
    print("decode window temp bytes", mem.temp_size_in_bytes,
          "resident", plan["resident"])
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the window's own buffers (the conv columns 0.62 GB, g float32 0.30,
    # k and u bf16 0.15 each: what ``_window_state_bytes`` sets aside),
    # their copies at the scan's edges and a step's temporaries; the state
    # pool is read where it lies and committed in place (the commit
    # kernel's result IS the pool, aliased to its operand)
    assert plan["runner"]._window_state_bytes() == 1_226_833_920
    assert mem.temp_size_in_bytes < 3.6e9
    assert mem.alias_size_in_bytes >= plan["resident"] - plan["weights"]
    assert [
        line for line in pool_sized_temporaries(compiled, plan)
        if "custom-call" not in line
    ] == []


@pytest.mark.parametrize("T", [256])
def test_one_row_prefill_compiles_with_the_chunk_form_and_flash(
    plan, silent_cache, T
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert ecfg.prefill_batch_size == 1 and T <= ecfg.prefill_chunk
    assert plan["mcfg"].num_heads // plan["mcfg"].num_kv_heads == 8
    MP = ecfg.max_pages_per_seq
    before = lowering.snapshot()
    forms = lowering.kda_counts()
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
    assert lowering.kda_counts()["chunked"] > forms["chunked"]
    mem = compiled.memory_analysis()
    print("prefill", T, "temp bytes", mem.temp_size_in_bytes)
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.alias_size_in_bytes >= plan["resident"] - plan["weights"]
    assert pool_sized_temporaries(compiled, plan) == []


@pytest.mark.parametrize("kind", ["kda"])
def test_reference_layer_compiles_on_the_served_weights(
    plan, silent_cache, kind
):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import kda_gqa_moe

    dims = tuple(sorted(kda_gqa_moe.dims_of(CFG).items()))
    arg = plan["arg"]
    h = arg((200, CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        traced = kda_gqa_moe._layer_jit.trace(
            dims, kind, plan["params"]["layers"], arg((), jnp.int32),
            arg((), jnp.int32), h, True, None, "both",
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # a layer's matrices up-cast to float32 one at a time, and every
    # state of the token scan (200 x 64 x 128 x 128 float32 = 0.84 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
