"""Compile for a described v5e (no chip attached) what the SDAR cell
runs, with the Pallas kernels lowered by Mosaic, and assert the bytes fit
one chip: the window of two blocks at the configuration's batch (the
paged kernel's BLOCK form: 4 positions x 32 heads = 128 query rows a row
over its pages and the window's earlier block; the grouped product over
the flat expert stack; the draw and the confidences over [512, 151,936]),
the one-row prefill at the buckets the traffic meets (flash attention
under the block mask), and the two programs of the forced forward, beside
the weights and the pool the configuration asks for. Guards the sizing of
perfbench/configs/sdar-30b-a3b-chat-l6-v5e1 before chip time is spent
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
from tests.perfbench.test_aot_mellum2_v5e import lowered_since

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads(
    (REPO / "perfbench/configs/sdar-30b-a3b-chat-l6-v5e1.json").read_text()
)


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
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    # the pool the chip run settled at (PERF.md section 6, PR 57): beside
    # the weights, the reserve and a denoising forward's logits
    cache = described(jax.eval_shape(lambda: alloc_cache(
        mcfg, ecfg, 1 + B * MP // 2, dtype=dtype,
    )))
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True          # what a TPU resolves the file's null to

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        weights=nbytes(params), pools=nbytes(cache),
        resident=nbytes(params) + nbytes(cache),
    )


def test_sizing_fills_the_chip_and_fits(plan):
    assert plan["weights"] == 2 * CFG["parameters"]
    assert plan["cache"].k_pages.shape == (6, 2049, 64, 512)
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] < 0.8 * HBM_LIMIT


def test_the_window_of_blocks_compiles_with_the_kernels_and_fits(
    plan, silent_cache
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg, Bk = plan["ecfg"], plan["arg"], plan["mcfg"].block_length
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.snapshot()
    rows = lowering.paged_decode_rows_per_step()
    traced = ModelRunner._decode_block_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B, Bk), jnp.int32), arg((B,), jnp.bool_), arg((B,), jnp.int32),
        arg((B, MP), jnp.int32), arg((2,), jnp.uint32),
        arg((B,), jnp.float32), arg((B,), jnp.float32), arg((B,), jnp.int32),
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B,), jnp.float32),
        ecfg.decode_multi_step // Bk,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    # the block form is the paged kernel's own trace, and no gather
    assert got["paged_decode"] >= 1 and got["kv_write"] == 1
    now = lowering.snapshot()
    assert now["paged_decode"]["reference"] == before["paged_decode"]["reference"]
    # 128 query rows a row: the largest block of rows the VMEM takes
    assert set(lowering.paged_decode_rows_per_step()) - set(rows) <= {1, 2, 4, 8}
    mem = compiled.memory_analysis()
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the logits of a denoising forward (311 MB in float32) and their
    # copies; no layer's 1.2 GB of experts is copied out of the stack
    assert mem.temp_size_in_bytes < 2.0e9
    assert mem.alias_size_in_bytes >= plan["pools"]


@pytest.mark.parametrize("T", [128, 1024])
def test_one_row_prefill_compiles_with_the_kernels_and_fits(
    plan, silent_cache, T
):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    MP = ecfg.max_pages_per_seq
    before = lowering.snapshot()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((1, T), jnp.int32), arg((1,), jnp.int32), arg((1, MP), jnp.int32),
        arg((1,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    assert got["flash_prefill"] >= 1 and got["kv_write"] == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.alias_size_in_bytes >= plan["pools"]


def test_the_forced_forwards_two_programs_compile(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner

    ecfg, arg, Bk = plan["ecfg"], plan["arg"], plan["mcfg"].block_length
    MP = ecfg.max_pages_per_seq
    for method, aliased in (
        (ModelRunner._decode_block_denoise_jit, False),
        (ModelRunner._decode_block_commit_jit, True),
    ):
        traced = method.trace(
            plan["runner"], plan["params"], plan["cache"],
            arg((1, Bk), jnp.int32), arg((1,), jnp.int32),
            arg((1, MP), jnp.int32),
        )
        mem = traced.lower(lowering_platforms=("tpu",)).compile().memory_analysis()
        assert mem.temp_size_in_bytes < 0.2e9
        assert (mem.alias_size_in_bytes >= plan["pools"]) == aliased
