"""Compile for a described v5e (no chip attached) what the
Phi-4-mini-flash-reasoning cell runs, with the Pallas kernels lowered by
Mosaic, and assert the bytes fit one chip: the fused decode window at
the configuration's batch (the paged kernel at the PAIR form, 40 queries
of 128 over 10 KV pairs, over the window pool and over the one full
layer's pool that eight layers read; the Mamba-1 step from the state the
scan carries; the commit from the window's tokens) and the one-row
prefill at the largest bucket the traffic meets ([1, 1024]: the flash
kernel at the pair form, the chunked scan, the in-place K/V write to
each pool), beside 7.71 GB of weights and the three pools
``ModelRunner._pages_that_fit`` hands out. Guards the sizing of
perfbench/configs/phi-4-mini-flash-reasoning-v5e1 before chip time is
spent (on-chip-measurement guide, section 2.3).

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
    (REPO / "perfbench/configs/phi-4-mini-flash-reasoning-v5e1.json").read_text()
)
#: what a v5e reports as its limit (15.75 GiB) to ``_pages_that_fit``
DEVICE_LIMIT = 15.75 * 2**30


@pytest.fixture(scope="module")
def plan():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import (
        alloc_cache, default_state_slots, pool_bytes, window_span_pages,
    )
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
    B, MP, PS = ecfg.decode_batch_size, ecfg.max_pages_per_seq, ecfg.kv_page_size
    span = window_span_pages(
        mcfg.sliding_window,
        (ecfg.decode_lookahead + 1) * ecfg.decode_multi_step, PS,
    )
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True
    # the pools ``_pages_that_fit`` hands out: the slots and the fused
    # window's buffers first, the window pool whole (a span a row of the
    # batch), the full pool what the limit leaves
    sized = pool_bytes(mcfg, ecfg, dtype)
    window_pages = 1 + B * span
    want = 1 + B * MP
    state = (1 + default_state_slots(ecfg, want)) * sized.slot_bytes
    avail = (
        DEVICE_LIMIT * (1 - HBM_RESERVE_FRACTION) - nbytes(params) - state
        - r._window_state_bytes() - window_pages * sized.window_page_bytes
    )
    pages = min(want, int(avail // sized.page_bytes) - _pool_margin_pages(
        MP, sized.margin_row_bytes))
    cache = described(jax.eval_shape(lambda: alloc_cache(
        mcfg, ecfg, pages, dtype=dtype, window_pages=window_pages,
    )))
    return dict(
        ecfg=ecfg, mcfg=mcfg, runner=r, params=params, arg=arg, cache=cache,
        span=span, pages=pages, weights=nbytes(params), pools=nbytes(cache),
        resident=nbytes(params) + nbytes(cache),
        window_state=r._window_state_bytes(),
    )


def lowered_since(before):
    from sutro_tpu.ops import lowering

    now = lowering.snapshot()
    return {k: now[k]["lowered"] - before[k]["lowered"] for k in now}


def test_sizing_fills_the_chip_and_fits(plan):
    # (A, D, the dt bias and the lambda vectors are float32)
    assert 2 * CFG["parameters"] < plan["weights"] < 2.001 * CFG["parameters"]
    c = plan["cache"]
    assert plan["span"] == 10
    # ONE full layer's pool, eight window layers at ten pages a row, nine
    # state layers a slot a row
    assert c.k_pages.shape[0] == 1 and c.k_pages.shape[2:] == (64, 1280)
    assert c.wk_pages.shape == (8, 1281, 64, 1280)
    assert c.ssm.shape == (9, 129, 16, 5120)
    assert c.ssm_conv.shape == (129, 9 * 3 * 5120)
    # the traffic holds about 1,700 full pages at 128 rows of a mean of
    # 800 tokens; every row at max_model_len would hold 4,096
    assert 2500 < plan["pages"] <= 1 + 128 * 32
    assert plan["resident"] > 0.7 * 16e9
    assert plan["resident"] + plan["window_state"] < 0.81 * DEVICE_LIMIT


def test_decode_window_compiles_at_the_pair_form_and_fits(plan, silent_cache):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    before = lowering.snapshot()
    forms = dict(lowering.mamba1_counts())
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    # the paged kernel a pool (a cross layer's call is the full layer's
    # own, traced once), the write once a pool; no call fell to the
    # gathered XLA form
    assert got["paged_decode"] >= 2 and got["kv_write"] == 2
    now = lowering.snapshot()
    assert now["paged_decode"]["reference"] == before["paged_decode"]["reference"]
    heads = lowering.kernel_heads_counts()
    assert heads["paged_decode@40"]["lowered"] >= 2
    assert heads["paged_decode@40"]["reference"] == 0
    assert lowering.mamba1_counts()["window"] > forms["window"]
    mem = compiled.memory_analysis()
    print("decode window temp", mem.temp_size_in_bytes, plan["resident"], plan["window_state"], plan["pages"])
    assert plan["resident"] + mem.temp_size_in_bytes < HBM_LIMIT, (
        plan["resident"], mem.temp_size_in_bytes
    )
    # the fused window's own buffers (the rows' state in float32 and a
    # step's new copy of it, the uncommitted tokens) and the sampler's
    # float32 logits: the pools are read where they lie
    assert mem.temp_size_in_bytes < plan["window_state"] + 1.2e9
    assert mem.alias_size_in_bytes >= plan["pools"]


@pytest.mark.parametrize("B,T", [(1, 1024)])
def test_prefill_compiles_at_the_pair_form_and_fits(plan, silent_cache, B, T):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.ops import lowering

    ecfg, arg = plan["ecfg"], plan["arg"]
    assert T <= ecfg.prefill_chunk
    MP = ecfg.max_pages_per_seq
    before = lowering.snapshot()
    forms = dict(lowering.mamba1_counts())
    traced = ModelRunner._prefill_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B, T), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((B,), jnp.int32),
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    got = lowered_since(before)
    assert got["flash_prefill"] >= 2   # a window layer's and a full one's
    now = lowering.snapshot()
    assert now["flash_prefill"]["reference"] == before["flash_prefill"]["reference"]
    assert now["kv_write"]["lowered"] >= 2 and now["kv_write"]["reference"] == 0
    assert lowering.mamba1_counts()["chunked"] > forms["chunked"]
    mem = compiled.memory_analysis()
    print("prefill temp", mem.temp_size_in_bytes)
    # the chunked scan holds [64, 16, 5120] float32 pairs a chunk and not
    # the 335 MB of [1024, 16, 5120]; the [1, 1024] stream and one
    # layer's projections beside it
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
    assert plan["resident"] + mem.temp_size_in_bytes < DEVICE_LIMIT
    assert mem.alias_size_in_bytes >= plan["pools"]
