"""Compile for a described v5e (no chip attached) what the JoyAI-LLM-Flash
cell runs, and assert the bytes fit one chip: the fused decode window at
the configuration's batch (the ABSORBED form over the gathered latent
pages) and the one-row prefill at the traffic's two buckets (the
EXPANDED form, a block of queries at a time), beside the weights (all 40
layers, 16 held experts a routed layer, the whole vocabulary) and ONE
latent pool ``[40, NP, 64, 640]`` (rows of 576 padded to whole lane
tiles) with no V pool, at the pool size the
runner would fit beside the weights; that neither program copies the
pool; and the plain reference's layers on the served weights. Guards the
sizing of perfbench/configs/joyai-llm-flash-ep16-v5e1 before chip time
is spent (on-chip-measurement guide, section 2.3).

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
import re
from pathlib import Path

import numpy as np
import pytest

from tests.perfbench.test_aot_v5e import HBM_LIMIT, silent_cache  # noqa: F401

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads((
    REPO / "perfbench/configs/joyai-llm-flash-ep16-v5e1.json"
).read_text())
#: what ``ModelRunner._pages_that_fit`` hands out on a v5e: the limit
#: (16.91 GB) less the weights and the 20 % reserve, over 3.28 MB a
#: page, less the kernel path's margin of 7 (my chip run, PR 42)
POOL_PAGES = 1199


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


def pool_copies(compiled, plan):
    """Values of the latent pool's size that the compiled program makes
    OUTSIDE a fusion and that are no view of the pool itself."""
    pool = plan["cache"].k_pages
    L, NP, PS, W = pool.shape
    shapes = "|".join((
        f"{L},{NP},{PS},{W}", f"{L * NP},{PS},{W}", f"{L * NP * PS},{W}",
    ))
    found = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", compiled.as_text()):
        if "fused_computation" in comp.split("\n", 1)[0]:
            continue
        for line in comp.splitlines()[1:]:
            m = re.match(
                r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(" + shapes
                + r")\]\S* (\w[\w\-]*)\(", line)
            if m and m.group(2) not in (
                "parameter", "get-tuple-element", "bitcast", "fusion",
                "while", "tuple", "scatter",
                "custom-call",      # the in-place write kernel (aliased)
            ):
                found.append(line.strip()[:160])
    return found


def test_sizing_fills_the_chip_and_fits(plan):
    # every leaf in bf16 but the 39 x 256 selection biases, in float32
    assert plan["weights"] == 2 * CFG["parameters"] + 2 * 39 * 256
    assert CFG["parameters"] == 4_776_521_472
    c = plan["cache"]
    # ONE pool over all 40 layers, a row of 512 latent values and the
    # 64-wide shared rotary key in 640 lanes; no V pool, no state
    assert c.k_pages.shape == (40, POOL_PAGES, 64, 640)
    assert c.v_pages is None and c.conv is None and c.ssm is None
    assert plan["pool"] == POOL_PAGES * 64 * 40 * 1280
    assert plan["resident"] > 0.25 * 16e9
    # the runner leaves a fifth of the device's 16.91e9 bytes (15.75
    # GiB; HBM_LIMIT reads the same figure as decimal GB and is the
    # stricter bound the programs below are held to)
    assert plan["resident"] <= 0.8 * 15.75 * 2**30


def test_decode_window_compiles_absorbed_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.latent_counts()
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    now = lowering.latent_counts()
    assert now["absorbed"] > before["absorbed"]
    assert now["expanded"] == before["expanded"]
    mem = compiled.memory_analysis()
    print("decode window temp bytes", mem.temp_size_in_bytes,
          "resident", plan["resident"])
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the pool is read by ONE gather a layer and committed in place
    assert mem.temp_size_in_bytes < 2.0e9
    assert mem.alias_size_in_bytes >= plan["pool"]
    assert pool_copies(compiled, plan) == []


@pytest.mark.parametrize("T", [2048, 4096])
def test_one_row_prefill_compiles_expanded_and_fits(plan, silent_cache, T):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert ecfg.prefill_batch_size == 1 and T <= ecfg.prefill_chunk
    MP = ecfg.max_pages_per_seq
    before = lowering.latent_counts()
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((1, T), jnp.int32), arg((1,), jnp.int32), arg((1, MP), jnp.int32),
        arg((1,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    now = lowering.latent_counts()
    assert now["expanded"] > before["expanded"]
    assert now["absorbed"] == before["absorbed"]
    mem = compiled.memory_analysis()
    print("prefill", T, "temp bytes", mem.temp_size_in_bytes)
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # a block of queries at a time: never [NH, T, T] float32 scores
    # (2.1 GB at T = 4,096)
    assert mem.temp_size_in_bytes < 2.0e9
    assert mem.alias_size_in_bytes >= plan["pool"]
    assert pool_copies(compiled, plan) == []


@pytest.mark.parametrize("routed", [False, True])
def test_reference_layer_compiles_on_the_served_weights(
    plan, silent_cache, routed
):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import mla_moe

    dims = tuple(sorted(mla_moe.dims_of(CFG).items()))
    arg = plan["arg"]
    h = arg((200, CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        traced = mla_moe._layer_jit.trace(
            dims, routed, plan["params"]["layers"], arg((), jnp.int32),
            arg((), jnp.int32), (h, arg((200,), jnp.int32)), True,
            "interleaved", "both",
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # a layer's matrices up-cast to float32 an expert at a time
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
