"""Compile for a described v5e (no chip attached) what the Xing4.0 cell
runs, and assert the bytes fit one chip: the fused decode window at the
configuration's batch of 128 (four lanes carried through the scan, the
ABSORBED latent form, the grouped kernel over 64 experts of 1,024) and
the one-row prefill at the traffic's two buckets (the EXPANDED form, the
lanes' passes at 4,096 tokens), beside the weights (7 layers, every
expert, the whole vocabulary) and ONE latent pool ``[7, NP, 64, 640]``
at the size the runner would fit beside the weights and the stream's
temporaries; that neither program copies the pool, and that the lanes
stay four arrays (no ``[.., 4, 3584]`` value, which the device would pad
to its tiles); and the plain reference's layers on the served weights.
Guards the sizing of perfbench/configs/xing4.0-29b-a4b-l7-v5e1 before
chip time is spent (on-chip-measurement guide, section 2.3).

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
import re
from pathlib import Path

import numpy as np
import pytest

from tests.perfbench.test_aot_joyai_v5e import pool_copies
from tests.perfbench.test_aot_v5e import HBM_LIMIT, silent_cache  # noqa: F401

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads((
    REPO / "perfbench/configs/xing4.0-29b-a4b-l7-v5e1.json"
).read_text())
#: what a v5e reports as its limit (15.75 GiB) to ``_pages_that_fit``
DEVICE_LIMIT = 15.75 * 2**30


@pytest.fixture(scope="module")
def plan():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import alloc_cache
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
    # the pool ``ModelRunner._pages_that_fit`` leaves room for: the limit
    # less the weights, four copies of a prefill chunk's stream and the
    # reserve, in pages of 7 x 64 x 640 x 2 bytes, less the kernel
    # path's margin
    page = mcfg.num_latent_layers * ecfg.kv_page_size * mcfg.page_width * 2
    stream = 4 * ecfg.prefill_chunk * mcfg.hc_mult * mcfg.hidden_size * 2
    avail = DEVICE_LIMIT * (1 - HBM_RESERVE_FRACTION) - nbytes(params) - stream
    pages = int(avail // page) - _pool_margin_pages(
        ecfg.max_pages_per_seq, ecfg.kv_page_size * mcfg.page_width * 2
    )
    cache = described(jax.eval_shape(
        lambda: alloc_cache(mcfg, ecfg, pages, dtype=dtype)
    ))
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = bool(ecfg.use_pallas)
    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        resident=nbytes(params) + nbytes(cache), weights=nbytes(params),
        pool=nbytes(cache), pages=pages,
    )


def padded_stream_values(compiled):
    """Values in the stream's dtype shaped ``[.., 4, 3584]`` (the lanes
    as an axis of one array, which the device pads to its tiles): the
    lanes are four arrays, so none. (The routed combine's float32
    ``[rows, top_k 4, 3584]`` is another matter.)"""
    return re.findall(r"= bf16\[[0-9,]*,4,3584\]", compiled.as_text())


def stream_ops(compiled):
    """``mhc_share_of_busy.hc_ops`` of a compiled program's own module:
    ``(own, shared)``, the names of the fusions wholly under an ``hc_``
    scope and of those that hold one beside another scope's
    instructions. The stream's readers count an op whole, so the
    program keeps the second list empty (``hc_sublayer``'s barriers)."""
    from perfbench.layer_metrics.mhc_share_of_busy import hc_ops

    module = (
        compiled.runtime_executable().hlo_modules()[0]
        .as_serialized_hlo_module_proto()
    )
    size, head = len(module), b""
    while True:                              # HloProto.hlo_module = 1
        head += bytes([size & 0x7F | (0x80 if size >> 7 else 0)])
        size >>= 7
        if not size:
            break
    kinds = hc_ops({"m": b"\x0a" + head + module})["m"]
    fusions = {k: v for k, v in kinds.items() if "fusion" in k}
    return (
        sorted(k for k, v in fusions.items() if v == "own"),
        sorted(k for k, v in fusions.items() if v == "shared"),
    )


def test_sizing_fills_the_chip_and_fits(plan):
    # every leaf in bf16 but the selection biases and the
    # hyper-connections' biases and alphas, in float32
    extra = 2 * (5 * 64 + 14 * (24 + 3))
    assert plan["weights"] == 2 * CFG["parameters"] + extra
    assert CFG["parameters"] == 4_920_866_746
    c = plan["cache"]
    assert c.k_pages.shape == (7, plan["pages"], 64, 640)
    assert c.v_pages is None and c.conv is None and c.ssm is None
    # over 5,000 pages: 128 rows of the traffic's mean 1.9 k tokens
    # take 3,900
    assert 5_000 < plan["pages"] < 6_000
    assert plan["resident"] > 0.25 * 16e9
    assert plan["resident"] <= 0.8 * DEVICE_LIMIT


def test_decode_window_compiles_with_four_lanes_and_fits(plan, silent_cache):
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
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.alias_size_in_bytes >= plan["pool"]
    assert pool_copies(compiled, plan) == []
    assert padded_stream_values(compiled) == []
    text = compiled.as_text()
    for scope in ("hc_coeff", "hc_sinkhorn", "hc_read", "hc_write", "mla_yarn"):
        assert scope in text
    own, shared = stream_ops(compiled)
    assert len(own) > 100 and shared == []


# the larger of the traffic's two buckets: what has to fit, and the same
# program but for its length (a compile is 25 s of the suite's time)
@pytest.mark.parametrize("T", [4096])
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
    # the lanes, their float32 sums and a block of queries at a time
    assert mem.temp_size_in_bytes < 1.2e9
    assert mem.alias_size_in_bytes >= plan["pool"]
    assert pool_copies(compiled, plan) == []
    assert padded_stream_values(compiled) == []
    own, shared = stream_ops(compiled)
    assert len(own) > 100 and shared == []


@pytest.mark.parametrize("routed", [True])       # the larger of the two
def test_reference_layer_compiles_on_the_served_weights(
    plan, silent_cache, routed
):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import mhc_mla_moe

    dims = tuple(sorted(mhc_mla_moe.dims_of(CFG).items()))
    arg = plan["arg"]
    X = arg((200, CFG["hc_mult"], CFG["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        traced = mhc_mla_moe._layer_jit.trace(
            dims, routed, plan["params"]["layers"], arg((), jnp.int32),
            arg((), jnp.int32), (X, arg((200,), jnp.int32)), (),
        )
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # a layer's matrices up-cast to float32 an expert at a time
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
