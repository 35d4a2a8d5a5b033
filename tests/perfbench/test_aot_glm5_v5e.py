"""Compile for a described v5e (no chip attached) what the GLM-5 cell
runs, and assert the bytes fit one chip: the fused decode window at the
configuration's batch over tables of 256 pages (the index keys of a
row's table gathered, the selection, the chosen latent rows fetched by
position; beside it in one program the dense latent path of a short
dispatch) and the one-row prefill at the traffic's two buckets, 8,192
and 16,384 (the EXPANDED form under the selection's mask, a block of
queries at a time), beside the weights (a dense and four routed layers,
16 held experts a routed layer, an eighth of the vocabulary) and BOTH
pools ``[5, NP, 64, 640]`` and ``[5, NP, 64, 128]`` at the worst-case
pool the configuration asks for; that neither program copies a pool.
Guards the sizing of perfbench/configs/glm-5-l5-ep16-v5e1 before chip
time is spent (on-chip-measurement guide, section 2.3).

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

from tests.perfbench.test_aot_joyai_v5e import pool_copies
from tests.perfbench.test_aot_v5e import HBM_LIMIT, silent_cache  # noqa: F401

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads((
    REPO / "perfbench/configs/glm-5-l5-ep16-v5e1.json"
).read_text())
#: the worst case the runner asks for and gets beside the weights: the
#: garbage page and 16 rows of 256 pages (my chip run, PR 46)
POOL_PAGES = 4097


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
    cache = described(jax.eval_shape(
        lambda: alloc_cache(mcfg, ecfg, POOL_PAGES, dtype=dtype)
    ))
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = bool(ecfg.use_pallas)

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        resident=nbytes(params) + nbytes(cache), weights=nbytes(params),
        pool=nbytes(cache),
    )


def test_sizing_fills_the_chip_and_fits(plan):
    # every leaf in bf16 but the 4 x 256 selection biases, in float32
    assert plan["weights"] == 2 * CFG["parameters"] + 2 * 4 * 256
    assert CFG["parameters"] == 3_909_632_768
    c = plan["cache"]
    assert c.k_pages.shape == (5, POOL_PAGES, 64, 640)
    assert c.ik_pages.shape == (5, POOL_PAGES, 64, 128)
    assert c.v_pages is None and c.conv is None and c.ssm is None
    assert plan["pool"] == POOL_PAGES * 491_520
    ecfg = plan["ecfg"]
    assert POOL_PAGES == 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] <= 0.8 * 15.75 * 2**30


def test_decode_window_compiles_both_branches_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.sparse_attention_counts()
    paged = lowering.snapshot()["paged_decode"]["lowered"]
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    now = lowering.sparse_attention_counts()
    # the selected rows by position, and in the same program the paged
    # kernel's latent variant for a dispatch at or under index_topk
    assert now["gathered"] > before["gathered"]
    assert now["masked"] == before["masked"]
    assert lowering.snapshot()["paged_decode"]["lowered"] > paged
    mem = compiled.memory_analysis()
    print("decode window temp bytes", mem.temp_size_in_bytes,
          "resident", plan["resident"])
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the index keys of 16 tables (67 MB a layer) and 16 x 2,048 rows,
    # never the latent rows of a whole table (0.34 GB a layer)
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.alias_size_in_bytes >= plan["pool"]
    assert pool_copies(compiled, plan) == []


@pytest.mark.parametrize("T", [8192, 16384])
def test_one_row_prefill_compiles_masked_and_fits(plan, silent_cache, T):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert ecfg.prefill_batch_size == 1 and T <= ecfg.prefill_chunk
    MP = ecfg.max_pages_per_seq
    before = lowering.sparse_attention_counts()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((1, T), jnp.int32), arg((1,), jnp.int32), arg((1, MP), jnp.int32),
        arg((1,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    now = lowering.sparse_attention_counts()
    assert now["masked"] > before["masked"]
    assert now["gathered"] == before["gathered"]
    mem = compiled.memory_analysis()
    print("prefill", T, "temp bytes", mem.temp_size_in_bytes)
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # a block of queries at a time: never [NH, T, T] float32 scores
    # (69 GB at T = 16,384) nor [T, T] index scores at once (1.07 GB)
    assert mem.temp_size_in_bytes < (2.6e9 if T == 8192 else 5.0e9)
    assert mem.alias_size_in_bytes >= plan["pool"]
    assert pool_copies(compiled, plan) == []


def test_the_short_prefill_is_the_flash_body_unpadded(plan, silent_cache):
    """A prompt at or under index_topk: the dense latent path, Q, K and V
    all 256 wide (no padded lanes)."""
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    before = lowering.snapshot()["flash_prefill"]
    sparse = lowering.sparse_attention_counts()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((1, 2048), jnp.int32), arg((1,), jnp.int32),
        arg((1, ecfg.max_pages_per_seq), jnp.int32), arg((1,), jnp.int32),
    )
    traced.lower(lowering_platforms=("tpu",)).compile()
    now = lowering.snapshot()["flash_prefill"]
    assert now["lowered"] > before["lowered"]
    assert now["reference"] == before["reference"]
    assert lowering.sparse_attention_counts() == sparse
