"""Compile for a described v5e (no chip attached) the fused decode window
of the dense 4B cell (``perfbench/configs/qwen3-4b-v5e1.json``: batch 64,
8 steps, both Pallas kernels) and read in the OPTIMIZED HLO which PART
of the model each instruction that runs carries
(``sutro_tpu/ops/lowering.py`` ``PARTS``): the paged decode kernel sits
under ``mixer``, the K/V write kernel under ``cache``, nothing heavy is
left without a part but what the layer scan does itself, and the share
of instructions without one is printed (``-s`` shows it). The CPU test
of the same rule is ``tests/test_part_scopes.py``; the chip's own split
in ms is ``perfbench/tools/part_table.py``'s.

The topology is described inside a fixture and the compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
from pathlib import Path

import pytest

from tests.perfbench.test_aot_v5e import silent_cache  # noqa: F401
from tests.test_part_scopes import (
    BOOKKEEPING, audit, check_kernels_under_their_parts,
)

REPO = Path(__file__).resolve().parents[2]
CFG = json.loads((REPO / "perfbench/configs/qwen3-4b-v5e1.json").read_text())
#: pages of the pool the compile is given: the program is the same at any
#: number, and the cell's own (what fits beside 8 GB of weights) is sized
#: by the runner at run time
PAGES = 400


@pytest.fixture(scope="module")
def window(silent_cache):  # noqa: F811
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
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    pool = arg(
        (mcfg.num_layers, PAGES, ecfg.kv_page_size,
         mcfg.num_kv_heads * mcfg.head_dim), dtype,
    )
    traced = ModelRunner._decode_multi_jit.trace(
        r, params, KVCache(k_pages=pool, v_pages=pool),
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    return traced.lower(lowering_platforms=("tpu",)).compile().as_text()


def test_the_windows_kernels_sit_under_mixer_and_cache(window):
    by_part, left, bad, rows = audit(window)
    kernels = [
        (part, name) for part, name, ins, _inner in rows
        if ins["opcode"] == "custom-call" and "tpu_custom_call" in ins["line"]
    ]
    assert sorted(p for p, _n in kernels) == ["cache", "mixer"], kernels
    seen = check_kernels_under_their_parts(
        name for _p, name, _i, _f in rows
    )
    assert seen == {"paged_decode_attention", "kv_write_pallas"}
    # nothing heavy is left without a part but what the scans do
    # themselves and the buffers the compiler allocates for their carries
    bad = [b for b in bad if "AllocateBuffer" not in b[1]]
    assert not bad, bad
    assert left <= BOOKKEEPING | {
        "copy-start", "copy-done", "custom-call", "dynamic-update-slice",
        "reduce",
    }, left - BOOKKEEPING
    assert {"embed", "mixer", "ffn", "cache", "head", "sample"} <= set(by_part)
    # what the chip's own table times (PERF.md section 5): instructions
    # that run, by part
    total = sum(by_part.values())
    print("\nqwen3-4b fused window, v5e: instructions that run, by part: "
          + ", ".join(f"{p or 'no part'} {n}" for p, n in by_part.most_common())
          + f"; without a part {100.0 * by_part[None] / total:.1f} %")
    assert by_part[None] / total < 0.25
