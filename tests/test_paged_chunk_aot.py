"""Compile for a described v5e (no chip attached) the two ``T > 1``
programs of the dense 4B cell over a paged past
(``perfbench/configs/qwen3-4b-v5e1.json``): ``_prefill_chunk_jit`` at
``[8, 256]`` (a job's admission: the suffix of 8 rows over the shared
prefix's pages) and ``_verify_cand_jit`` at ``[64, 17]`` (the
fast-forward over a schema's forced tokens), and read in the OPTIMIZED
HLO what their attention became: the chunk kernel's Mosaic call is
there (``ops/pallas_chunk.py``: the row's pages read where they lie), no
array of a row's whole gathered table ``[B, MP x PS, ...]`` and no float32
score tensor ``[B, KVH, G, T, S]`` is, and the chunk program's
temporaries are below what the gathering program took by more than the
gathered tables (the verify program's are the head's, ``[64, 17,
151,936]`` logits in float32: the gather lay under them, and they are
what they were).

The topology is described inside a fixture and the compiles run in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
import re
from pathlib import Path

import pytest

from tests.perfbench.test_aot_v5e import silent_cache  # noqa: F401

REPO = Path(__file__).resolve().parents[1]
CFG = json.loads((REPO / "perfbench/configs/qwen3-4b-v5e1.json").read_text())
#: pages of the pool the compile is given: the program is the same at any
PAGES = 400
#: ``temp_size_in_bytes`` of the same two programs at the parent of the
#: PR that brought the kernel (commit 0ee2e69: the gather, float32
#: products, the scores through HBM), compiled here the same way
TEMP_BEFORE = {"chunk": 968_828_416, "verify": 1_222_672_384}
SHAPES = {"chunk": (8, 256), "verify": (64, 17)}


@pytest.fixture(scope="module")
def programs(silent_cache):  # noqa: F811
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
    MP = ecfg.max_pages_per_seq
    pool = arg(
        (mcfg.num_layers, PAGES, ecfg.kv_page_size,
         mcfg.num_kv_heads * mcfg.head_dim), dtype,
    )
    cache = KVCache(k_pages=pool, v_pages=pool)

    def rows(B, T):
        # ids, valid_len, page_table, start
        return (arg((B, T), jnp.int32), arg((B,), jnp.int32),
                arg((B, MP), jnp.int32), arg((B,), jnp.int32))

    B, T = SHAPES["verify"]
    traced = {
        "chunk": ModelRunner._prefill_chunk_jit.trace(
            r, params, cache, *rows(*SHAPES["chunk"])
        ),
        # the candidates of a position: [B, C, 32] ids and their counts
        "verify": ModelRunner._verify_cand_jit.trace(
            r, params, cache, *rows(B, T),
            arg((B, T, 32), jnp.int32), arg((B, T), jnp.int32),
        ),
    }
    out = {}
    for name, t in traced.items():
        compiled = t.lower(lowering_platforms=("tpu",)).compile()
        out[name] = dict(
            text=compiled.as_text(),
            temp=compiled.memory_analysis().temp_size_in_bytes,
        )
    out["sizes"] = dict(
        MP=MP, PS=ecfg.kv_page_size, KVH=mcfg.num_kv_heads,
        G=mcfg.num_heads // mcfg.num_kv_heads, Dh=mcfg.head_dim,
    )
    return out


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_program_reads_the_pages_in_place(programs, name):
    text, z = programs[name]["text"], programs["sizes"]
    B, T = SHAPES[name]
    S = z["MP"] * z["PS"]
    # the chunk kernel's Mosaic call, in the layer scan's body
    calls = [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        and line.lstrip().startswith("%paged_chunk_attention")
    ]
    assert calls, "no Mosaic call of the chunk kernel in the program"
    # no array of every row's whole table: [B, MP x PS, KD] or its head
    # split [B, MP x PS, KVH, Dh], [B, MP, PS, KD], in any dtype ...
    KD = z["KVH"] * z["Dh"]
    gathered = [
        f"[{B},{S},{KD}]", f"[{B},{S},{z['KVH']},{z['Dh']}]",
        f"[{B},{z['MP']},{z['PS']},{KD}]", f"[{B * z['MP']},{z['PS']},{KD}]",
    ]
    found = sorted({g for g in gathered if g in text})
    assert not found, f"a gathered table in the program: {found}"
    # ... and no float32 scores over the table and the chunk
    scores = re.findall(
        rf"f32\[{B},{z['KVH']},{z['G']},{T},\d+\]", text
    )
    assert not scores, sorted(set(scores))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_temporaries_do_not_grow_and_the_chunks_fall(programs, name):
    z = programs["sizes"]
    B, _T = SHAPES[name]
    temp = programs[name]["temp"]
    assert temp <= TEMP_BEFORE[name], (name, temp, TEMP_BEFORE[name])
    if name == "chunk":
        # K and V of every row's table in bfloat16: the least the gather
        # held (it held 363 MB: the float32 scores beside them)
        tables = 2 * B * z["MP"] * z["PS"] * z["KVH"] * z["Dh"] * 2
        assert temp <= TEMP_BEFORE[name] - tables, (
            temp, TEMP_BEFORE[name], tables
        )
