"""The KV pool is read in place: no consumer is handed a per-layer slice.

The stacked pool ``[L, NP, PS, KD]`` is a constant of the layer scan, the
scan carries the layer's index, and every reader indexes ``[layer, page]``
itself (models/transformer.py, ops/attention.py, ops/pallas_paged.py). A
pool among the scan's ``xs`` reaches the Mosaic call as a dynamic slice,
which XLA copies out in full before the call: 76 MB a layer, K and V, every
layer of every decode step at the 4B cell's pool (PERF.md, PR 25).

Three kinds of test: (a) the kernels' parity on a stacked pool, reading a
middle layer, in interpret mode; (b) the jaxpr of the forward and of the
runner's fused window: the pool is a scan constant at every depth; (c) the
decode window compiled for a described v5e:2x2 holds no op whose result
has the per-layer pool's shape. The topology is described inside a
fixture and the compile runs in the test's own process.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.engine.kvcache import KVCache, _quantize_tokens, gather_kv_layer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops.attention import chunk_attention
from sutro_tpu.ops.pallas_paged import (
    paged_decode_attention,
    prefix_attention_carry,
    prefix_attention_carry_pallas,
)

# ---------------------------------------------------------------------------
# (a) parity on a stacked pool, middle layer
# ---------------------------------------------------------------------------

L, B, NH, KVH, DH, PS, MP, NP = 4, 4, 4, 2, 16, 8, 6, 40
KD = KVH * DH
N_PFX = 3  # shared-prefix pages at the head of rows 0..2's tables

CASES = {
    "chunk1": dict(),
    "chunk2": dict(group=2),
    "chunk4_window5": dict(group=4, window=5),
    "window_buffer": dict(win_buf=True),
    "window_buffer_chunk2": dict(win_buf=True, group=2),
    "int8": dict(int8=True),
    "int8_chunk2_window_buffer": dict(int8=True, group=2, win_buf=True),
    "prefix_carry_xla": dict(prefix="xla"),
    "prefix_carry_pallas": dict(prefix="pallas", window=7),
    "prefix_carry_int8": dict(prefix="xla", int8=True),
}


def _tables(prefix: bool) -> np.ndarray:
    """Ascending contiguous runs a row; in
    prefix mode rows 0..2 start with the shared pages [1, 2, 3]."""
    table = np.zeros((B, MP), np.int32)
    nxt = 1 + (N_PFX if prefix else 0)
    for b in range(B):
        own = MP - N_PFX if (prefix and b < 3) else MP
        if own < MP:
            table[b, :N_PFX] = np.arange(1, 1 + N_PFX)
        table[b, MP - own:] = np.arange(nxt, nxt + own)
        nxt += own
    assert nxt <= NP
    return table


@pytest.mark.parametrize("layer", [1, L - 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_decode_reads_its_layer_of_the_stack(case, layer, paged_ring):
    """``paged_decode_attention`` on the whole stack + a layer index equals
    ``gather_kv_layer`` of that layer + the jnp attention, and differs
    from what any other layer would give. ``group`` pages a compute
    group, in a ring of two groups (it wraps); else one page a group."""
    spec = CASES[case]
    paged_ring(2 * spec.get("group", 1), spec.get("group", 1))
    rng = np.random.default_rng(25)
    q = jnp.asarray(rng.standard_normal((B, 1, NH, DH)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, DH)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, DH)), jnp.float32)
    kf = jnp.asarray(rng.standard_normal((L, NP, PS, KD)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((L, NP, PS, KD)), jnp.float32)
    ks = vs = None
    if spec.get("int8"):
        kf, ks = _quantize_tokens(kf)
        vf, vs = _quantize_tokens(vf)
    prefix = spec.get("prefix")
    table = jnp.asarray(_tables(bool(prefix)))
    past = np.array([N_PFX * PS + 5, N_PFX * PS + 11, N_PFX * PS + 2, 17])
    past_len = jnp.asarray(past, jnp.int32)
    win = jnp.asarray(spec.get("window", 0), jnp.int32)
    lyr = jnp.asarray(layer, jnp.int32)
    wkw = {}
    win_len = jnp.asarray(0, jnp.int32)
    if spec.get("win_buf"):
        win_len = jnp.asarray(5, jnp.int32)
        wkw = dict(
            win_k=jnp.asarray(rng.standard_normal((B, 8, KD)), jnp.float32),
            win_v=jnp.asarray(rng.standard_normal((B, 8, KD)), jnp.float32),
            win_len=win_len,
        )

    def reference(at_layer):
        gk, gv = gather_kv_layer(
            kf, vf, jnp.asarray(at_layer, jnp.int32), table, KVH,
            k_scale=ks, v_scale=vs,
        )
        return chunk_attention(
            q, k_cur, v_cur,
            positions=(past_len + win_len)[:, None],
            valid_len=jnp.ones((B,), jnp.int32),
            past_k=gk, past_v=gv, past_len=past_len, window=win, **wkw,
        )[:, 0]

    carry = {}
    if prefix:
        pfx_len = jnp.asarray([N_PFX * PS] * 3 + [0], jnp.int32)
        fn = (
            functools.partial(prefix_attention_carry, k_scale=ks, v_scale=vs)
            if prefix == "xla"
            else functools.partial(
                prefix_attention_carry_pallas, interpret=True
            )
        )
        m0, l0, acc0 = fn(
            q[:, 0], kf, vf, lyr, jnp.arange(1, 1 + N_PFX, dtype=jnp.int32),
            pfx_len, past_len, win,
        )
        carry = dict(pfx_cnt=pfx_len // PS, m0=m0, l0=l0, acc0=acc0)

    got = paged_decode_attention(
        q[:, 0], kf, vf, lyr, table, past_len, k_cur[:, 0], v_cur[:, 0],
        win, None, interpret=True,
        k_scale=ks, v_scale=vs, **wkw, **carry,
    )
    want = reference(layer)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5
    )
    # the comparison can tell layers apart: layer 0 reads other bytes
    assert np.abs(np.asarray(reference(0)) - np.asarray(want)).max() > 1e-2


# ---------------------------------------------------------------------------
# (b) structure: the pool is a constant of every scan it passes through
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):  # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _scans_with(jaxpr, shapes, found):
    """Every scan at any depth with an operand of one of ``shapes``, as
    (role, shape) pairs: role is ``const``, ``carry`` or ``xs``. A per-
    layer slice of a pool (the shape less its leading axis) counts too."""
    sliced = {s[1:] for s in shapes}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            for i, var in enumerate(eqn.invars):
                shape = tuple(getattr(var.aval, "shape", ()))
                role = "const" if i < nc else (
                    "carry" if i < nc + nk else "xs"
                )
                if shape in shapes or shape in sliced:
                    found.append((role, shape))
        for sub in _sub_jaxprs(eqn):
            _scans_with(sub, shapes, found)
    return found


def _tiny_paged(quantized: bool):
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import alloc_cache

    mcfg = MODEL_CONFIGS["tiny-dense"]
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=4, decode_batch_size=2,
        max_model_len=32, use_pallas=False, param_dtype="float32",
        activation_dtype="float32",
        kv_quantize="int8" if quantized else None,
    )
    cache = alloc_cache(mcfg, ecfg, num_pages=11)
    params = jax.eval_shape(
        functools.partial(transformer.init_params, mcfg, dtype=jnp.float32),
        jax.random.PRNGKey(0),
    )
    return mcfg, ecfg, cache, params


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("t", [1, 8])
def test_forward_scans_the_layer_index_not_the_pool(quantized, t):
    """jaxpr of ``transformer.forward`` with a paged past (decode T=1 and a
    chunk T=8): the pool, and in int8 mode its scales, enter the layer
    scan as constants; nothing of their shape is a carry or among xs."""
    mcfg, ecfg, cache, params = _tiny_paged(quantized)
    bsz, mp = 2, ecfg.max_pages_per_seq
    table = jnp.zeros((bsz, mp), jnp.int32)

    def fwd(params, cache, ids, pos, valid, past):
        return transformer.forward(
            mcfg, params, ids, pos, valid,
            paged_past=(
                (cache.k_pages, cache.v_pages, cache.k_scale,
                 cache.v_scale, table)
                if quantized else (cache.k_pages, cache.v_pages, table)
            ),
            past_len=past,
        )

    jaxpr = jax.make_jaxpr(fwd)(
        params, cache, jnp.zeros((bsz, t), jnp.int32),
        jnp.zeros((bsz, t), jnp.int32), jnp.ones((bsz,), jnp.int32),
        jnp.zeros((bsz,), jnp.int32),
    )
    shapes = {tuple(cache.k_pages.shape)}
    if quantized:
        shapes.add(tuple(cache.k_scale.shape))
    found = _scans_with(jaxpr.jaxpr, shapes, [])
    assert found, "the layer scan does not see the pool at all"
    assert {role for role, _ in found} == {"const"}, found
    # K and V (and both scales) reach the one layer scan whole
    assert sorted(s for _, s in found) == sorted(2 * list(shapes)), found


def test_fused_window_keeps_the_pool_constant_through_both_scans(tiny_ecfg):
    """The runner's fused decode window nests the layer scan inside the
    step scan: the pool is a constant of both (a carried pool is copied
    per step, PERF.md "PR 21 and earlier"), while the small window
    buffers are the step scan's carry and the layer scan's xs."""
    from sutro_tpu.engine.runner import ModelRunner

    runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], tiny_ecfg)
    bsz, mp = tiny_ecfg.decode_batch_size, tiny_ecfg.max_pages_per_seq
    steps = 3

    def window(params, cache, last, past, table, rng, temp, top_p, top_k):
        return runner._window_scan(
            params, cache, last, past, table, rng, temp, top_p, steps, top_k
        )

    jaxpr = jax.make_jaxpr(window)(
        runner.params, runner.cache, jnp.zeros((bsz,), jnp.int32),
        jnp.zeros((bsz,), jnp.int32), jnp.zeros((bsz, mp), jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((bsz,), jnp.float32),
        jnp.ones((bsz,), jnp.float32), jnp.zeros((bsz,), jnp.int32),
    )
    pool = tuple(runner.cache.k_pages.shape)
    found = _scans_with(jaxpr.jaxpr, {pool}, [])
    # K and V, in the step scan and in the layer scan inside it
    assert found == [("const", pool)] * 4, found
    wbuf = (pool[0], bsz, steps, pool[3])
    roles = {r for r, _ in _scans_with(jaxpr.jaxpr, {wbuf}, [])}
    assert roles == {"carry", "xs"}, roles


def test_pipeline_stage_loop_indexes_its_local_pool():
    """parallel/pipeline.py's stage loop goes through the same
    ``layer_apply`` signature: the stage-local pool is a constant of the
    stage's layer scan and the scan carries the local layer index."""
    from jax.sharding import Mesh

    from sutro_tpu.parallel.pipeline import pipeline_decode

    mcfg, ecfg, cache, params = _tiny_paged(False)
    if len(jax.devices()) < 2 or mcfg.num_layers % 2:
        pytest.skip("needs two devices and an even layer count")
    mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
    bsz, mp = 2, ecfg.max_pages_per_seq

    def step(params, cache, ids, pos, valid, table, past):
        return pipeline_decode(
            mcfg, params, ids, pos, valid, cache.k_pages, cache.v_pages,
            table, past, mesh,
        )

    jaxpr = jax.make_jaxpr(step)(
        params, cache, jnp.zeros((bsz, 1), jnp.int32),
        jnp.zeros((bsz, 1), jnp.int32), jnp.ones((bsz,), jnp.int32),
        jnp.zeros((bsz, mp), jnp.int32), jnp.zeros((bsz,), jnp.int32),
    )
    full = tuple(cache.k_pages.shape)
    local = (full[0] // 2,) + full[1:]
    found = _scans_with(jaxpr.jaxpr, {local}, [])
    assert found and {role for role, _ in found} == {"const"}, found


# ---------------------------------------------------------------------------
# (c) compiled for a described v5e:2x2: no op of the per-layer pool's shape
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def silent_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def test_tp4_decode_window_hlo_has_no_per_layer_pool(topo, silent_cache):
    """Qwen3-8B widths on a 2x2 mesh (tp=4), cut to 4 layers and a
    257-page pool so the compile takes seconds: the decode window holds
    the Pallas kernel and the collectives, the stacked pool only as the
    program's own parameters and aliased outputs, and no instruction
    whose result is one layer's ``[NP, PS, KD/tp]`` pool (a pool among
    the scan's xs shows here as ``dynamic-slice_bitcast`` fusions)."""
    import dataclasses
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.parallel.mesh import auto_mesh
    from sutro_tpu.parallel.sharding import cache_shardings, param_shardings

    mcfg = dataclasses.replace(MODEL_CONFIGS["qwen3-8b"], num_layers=4)
    ecfg = EngineConfig(
        kv_page_size=64, max_pages_per_seq=16, decode_batch_size=16,
        max_model_len=1024, decode_multi_step=4, tp=4, use_pallas=True,
    )
    mesh = auto_mesh(ecfg, devices=list(topo.devices))
    dtype = jnp.dtype(ecfg.param_dtype)
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, mcfg, dtype=dtype),
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, param_shardings(shapes, mesh),
    )
    rep = NamedSharding(mesh, P())
    tp = 4
    pages, kd = 257, mcfg.num_kv_heads * mcfg.head_dim
    pool = jax.ShapeDtypeStruct(
        (mcfg.num_layers, pages, ecfg.kv_page_size, kd), dtype,
        sharding=cache_shardings(mesh, mcfg.num_kv_heads),
    )
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, mesh
    r.sp = r.pp = 1
    r.ep_mesh = None
    r.use_pallas, r.kernel_mesh = True, mesh
    bsz, mp = ecfg.decode_batch_size, ecfg.max_pages_per_seq

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    traced = ModelRunner._decode_multi_jit.trace(
        r, params, KVCache(k_pages=pool, v_pages=pool),
        arg((bsz,), jnp.int32), arg((bsz,), jnp.int32),
        arg((bsz, mp), jnp.int32), arg((2,), jnp.uint32),
        arg((bsz,), jnp.float32), arg((bsz,), jnp.float32),
        ecfg.decode_multi_step, arg((bsz,), jnp.int32), 1, None,
    )
    text = traced.lower(lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    per_layer = f"[{pages},{ecfg.kv_page_size},{kd // tp}]"
    stacked = f"[{mcfg.num_layers},{pages},{ecfg.kv_page_size},{kd // tp}]"
    assert stacked in text  # the check below looks at the right shapes
    result = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+(\[[\d,]*\])")
    offenders = [
        line.strip()[:160]
        for line in text.splitlines()
        if (m := result.match(line)) and m.group(1) == per_layer
    ]
    assert not offenders, offenders
