"""Compile for a described v5e (no chip attached) what the granite cell
runs, and assert the bytes fit one chip: the fused decode window at the
configuration's batch and the batched prefill at its widest shape, beside
the weights, the worst-case pool over the four attention layers and the
state pool of a slot a batch row; that neither program holds a copy of
the state pool; and the plain reference's mamba layer on the served
weights. Guards the sizing of perfbench/configs/granite-4.0-h-micro-v5e1
before chip time is spent (on-chip-measurement guide, section 2.3).

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
CFG = json.loads(
    (REPO / "perfbench/configs/granite-4.0-h-micro-v5e1.json").read_text()
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
    pages = 1 + ecfg.decode_batch_size * ecfg.max_pages_per_seq
    cache = described(jax.eval_shape(
        lambda: alloc_cache(mcfg, ecfg, pages, dtype=dtype)
    ))
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
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        resident=nbytes(params) + nbytes(cache), weights=nbytes(params),
        state=nbytes((cache.ssm, cache.ssm_conv)),
    )


def pool_sized_temporaries(compiled, plan):
    """Values of the state pool's size that the compiled program makes
    OUTSIDE a fusion and that are no view of the pool itself: a copy,
    or a gather of every slot."""
    pool = plan["cache"].ssm
    shape = ",".join(str(d) for d in pool.shape)
    flat = f"{pool.shape[0] * pool.shape[1]},{pool.shape[2]},{pool.shape[3]}"
    found = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", compiled.as_text()):
        if "fused_computation" in comp.split("\n", 1)[0]:
            continue
        for line in comp.splitlines()[1:]:
            m = re.match(
                r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(" + shape + "|" + flat
                + r")\]\S* (\w[\w\-]*)\(", line)
            if m and m.group(2) not in (
                "parameter", "get-tuple-element", "bitcast", "fusion",
                "while", "tuple",
            ):
                found.append(line.strip()[:160])
    return found


def test_sizing_fills_the_chip_and_fits(plan):
    # 3,191,396,096 parameters in bf16, the 3 x 36 x 64 per-head scalars
    # in float32
    assert plan["weights"] == 2 * 3_191_396_096 + 2 * 3 * 36 * 64
    c = plan["cache"]
    assert c.k_pages.shape[0] == 4 and c.conv is None
    # a slot a row of the batch and the garbage slot: 4.99 GB of state
    # beside 1.07 GB of K/V
    assert c.ssm.shape == (36, 129, 128, 4096)
    assert 4.95e9 < plan["state"] < 5.0e9
    assert 1.0e9 < plan["resident"] - plan["weights"] - plan["state"] < 1.1e9
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] < 0.8 * HBM_LIMIT


def test_decode_window_compiles_fits_and_holds_no_copy_of_the_state(
    plan, silent_cache
):
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
    # the window reads the pool where it lies and commits it in place:
    # its temporaries are smaller than the pool, and the donated pool
    # comes back as the same buffer
    assert mem.temp_size_in_bytes < plan["state"]
    assert mem.alias_size_in_bytes >= plan["resident"] - plan["weights"]
    assert pool_sized_temporaries(compiled, plan) == []
    # a step reads the committed state through the kernel (the
    # configuration's ``kernels``)
    text = compiled.as_text()
    assert "ssm_state_read" in CFG["kernels"]
    assert "tpu_custom_call" in text and "ssm_state_read" in text


@pytest.mark.parametrize("rows", [None, 8], ids=["the cell's rows", "8 rows"])
def test_widest_prefill_compiles_fits_and_scatters_into_the_pool(
    plan, silent_cache, rows
):
    """At the configuration's ``prefill_batch_size`` (a row alone), and
    at the engine's default of 8 that ISSUE 32 sized the chip for."""
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner

    ecfg, arg = plan["ecfg"], plan["arg"]
    B = rows or ecfg.prefill_batch_size
    T, MP = ecfg.prefill_chunk, ecfg.max_pages_per_seq
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
    assert mem.temp_size_in_bytes < plan["state"]
    assert mem.alias_size_in_bytes >= plan["resident"] - plan["weights"]
    assert pool_sized_temporaries(compiled, plan) == []


def test_reference_mamba_layer_compiles_on_the_served_weights(plan, silent_cache):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import granite_hybrid

    dims = tuple(sorted(granite_hybrid.dims_of(CFG).items()))
    arg = plan["arg"]
    h = arg((200, CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        traced = granite_hybrid._layer_jit.trace(
            dims, plan["params"]["layers"], "mamba",
            arg((), jnp.int32), arg((), jnp.int32), h, arg((200,), jnp.int32),
            False,
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # one layer's matrices up-cast to float32, and a state of 2 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
