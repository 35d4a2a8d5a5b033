"""Compile for a described v5e (no chip attached) what the LFM2 cell
runs, and assert the bytes fit one chip: the fused decode window at the
configuration's batch and the batched prefill at its widest shape, beside
the weights, the worst-case pool over the two attention layers and the
conv state; and the plain reference's routed layer on the served weights.
Guards the sizing of perfbench/configs/lfm2-24b-a2b-l10-v5e1 before chip
time is spent (on-chip-measurement guide, section 2.3).

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
    (REPO / "perfbench/configs/lfm2-24b-a2b-l10-v5e1.json").read_text()
)


@pytest.fixture(scope="module")
def plan():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import KVCache
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

    params = jax.tree.map(
        lambda s: arg(s.shape, s.dtype),
        jax.eval_shape(
            functools.partial(transformer.init_params, mcfg, dtype=dtype),
            jax.random.PRNGKey(0),
        ),
    )
    pages = 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
    pool = arg((mcfg.num_attn_layers, pages, ecfg.kv_page_size,
                mcfg.num_kv_heads * mcfg.head_dim), dtype)
    state = arg((pages, mcfg.num_conv_layers * mcfg.conv_state_len * mcfg.hidden_size), dtype)
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    # what ``use_pallas: null`` resolves to on the chip: every call's own
    # shape gate then picks its path
    assert CFG["engine"]["use_pallas"] is None
    r.use_pallas = True

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg,
        cache=KVCache(k_pages=pool, v_pages=pool, conv=state),
        resident=nbytes(params) + 2 * nbytes(pool) + nbytes(state),
        weights=nbytes(params),
    )


def test_sizing_fills_the_chip_and_fits(plan):
    # 5,267,090,176 parameters in bf16 (the 512 selection biases in float32)
    assert plan["weights"] == 2 * 5_267_090_176 + 2 * 8 * 64
    # the pool spans the two attention layers: 1.07 GB, not 5.4 GB
    assert plan["cache"].k_pages.shape[0] == 2
    assert 1.3e9 < plan["resident"] - plan["weights"] < 1.4e9
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] < 0.8 * HBM_LIMIT


def test_decode_window_compiles_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    mem = compiled.memory_analysis()
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the routed layers' products are the grouped kernel, none of them
    # XLA's ragged product (the configuration's ``kernels``)
    text = compiled.as_text()
    assert "grouped_matmul" in CFG["kernels"]
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    assert "ragged-dot" not in text


def test_widest_prefill_compiles_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, T, MP = ecfg.prefill_batch_size, ecfg.prefill_chunk, ecfg.max_pages_per_seq
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B, T), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((B,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    mem = compiled.memory_analysis()
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )


def test_reference_routed_layer_compiles_on_the_served_weights(plan, silent_cache):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    dims = tuple(sorted(lfm2_moe.dims_of(CFG).items()))
    arg = plan["arg"]
    h = arg((200, CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        traced = lfm2_moe._layer_jit.trace(
            dims, "conv", "moe", plan["params"]["layers"],
            arg((), jnp.int32), arg((), jnp.int32), h, arg((200,), jnp.int32),
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # one expert's three matrices up-cast to float32 at a time
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
