"""The XLA paged-decode path (``ops/attention.paged_decode_xla``): decode
attention for every call the Pallas kernel does not take, computed on the
gathered pages in the layout the pool gave them.

Four kinds of test: (a) the function against the general path as its
oracle (``gather_kv_layer`` + ``chunk_attention`` with explicit past K/V
at ``T == 1``), float32 on the CPU; (b) the jaxpr of the fused decode
window of the tiny LFM2 and granite presets holds one gather of K and one
of V an attention mixer, no concatenation of anything context-sized and
no float32 array of the gathered context's shape; (c) the trace-time
count; (d) the granite cell's decode window compiled for a described v5e
keeps less scratch than the parent's program did. The topology is
described inside a fixture and the compile runs in the test's own process.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import gather_kv_layer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import lowering
from sutro_tpu.ops.attention import chunk_attention, paged_decode_xla
from tests.perfbench.test_aot_granite_v5e import plan, silent_cache  # noqa: F401
from tests.test_pool_in_place import _sub_jaxprs

# ---------------------------------------------------------------------------
# (a) against the general path
# ---------------------------------------------------------------------------

L, B, KVH, PS, MP, W = 3, 6, 2, 8, 5, 4
S = MP * PS
LAYER = 1
# ragged pasts: nothing, a few tokens, page-aligned, mid page, a full
# table, one token
PAST = np.array([0, 5, 2 * PS, 19, S, 1], np.int32)

SCENARIOS = {
    "no_window_buffer": dict(buf=False),
    "win_len_0": dict(win_len=0),
    "win_len_3": dict(win_len=3),
    "win_len_full": dict(win_len=W),
    "sliding_window_shorter_than_past": dict(win_len=3, window=7),
    "sliding_window_no_buffer": dict(buf=False, window=4),
    "sink": dict(win_len=3, sink=True),
    "sink_and_sliding_window": dict(win_len=W, window=11, sink=True),
    "int8_scales": dict(win_len=3, int8=True),
    "int8_sliding_window_sink": dict(buf=False, window=9, sink=True, int8=True),
}


def _operands(dh, g, *, buf=True, win_len=0, window=0, sink=False, int8=False):
    rng = np.random.default_rng(dh * 131 + g)
    nh, kd = KVH * g, KVH * dh
    n_pages = 1 + B * MP
    # page 0 is the garbage page every table is padded with: it holds
    # large values, so a mask that lets it through shows
    if int8:
        k_pages = rng.integers(-127, 128, (L, n_pages, PS, kd)).astype(np.int8)
        v_pages = rng.integers(-127, 128, (L, n_pages, PS, kd)).astype(np.int8)
        k_scale = rng.uniform(0.005, 0.02, (L, n_pages, PS)).astype(np.float32)
        v_scale = rng.uniform(0.005, 0.02, (L, n_pages, PS)).astype(np.float32)
        k_scale[:, 0] = v_scale[:, 0] = 1.0
    else:
        k_pages = rng.standard_normal((L, n_pages, PS, kd)).astype(np.float32)
        v_pages = rng.standard_normal((L, n_pages, PS, kd)).astype(np.float32)
        k_pages[:, 0] = 50.0
        v_pages[:, 0] = 1e4
        k_scale = v_scale = None
    table = np.zeros((B, MP), np.int32)
    for b in range(B):
        held = -(-int(PAST[b]) // PS)
        # scattered, not ascending: the last page first
        table[b, :held] = (1 + b * MP + np.arange(held))[::-1]
    ops = dict(
        q=rng.standard_normal((B, nh, dh)).astype(np.float32),
        k_pages=k_pages, v_pages=v_pages,
        layer=np.int32(LAYER), page_table=table, past_len=PAST,
        k_cur=rng.standard_normal((B, KVH, dh)).astype(np.float32),
        v_cur=rng.standard_normal((B, KVH, dh)).astype(np.float32),
        window=np.int32(window),
    )
    if sink:
        ops["sink"] = rng.standard_normal((nh,)).astype(np.float32)
    if buf:
        ops["win_k"] = rng.standard_normal((B, W, kd)).astype(np.float32)
        ops["win_v"] = rng.standard_normal((B, W, kd)).astype(np.float32)
        ops["win_len"] = np.int32(win_len)
    if int8:
        ops["k_scale"], ops["v_scale"] = k_scale, v_scale
    return {k: jnp.asarray(v) for k, v in ops.items()}


def _oracle(ops):
    """The general path: the layer's pages gathered and head-split,
    concatenated with the window's buffer and the token, one softmax."""
    past_k, past_v = gather_kv_layer(
        ops["k_pages"], ops["v_pages"], ops["layer"], ops["page_table"],
        KVH, k_scale=ops.get("k_scale"), v_scale=ops.get("v_scale"),
    )
    q_pos = ops["past_len"] + ops.get("win_len", 0)
    return chunk_attention(
        ops["q"][:, None], ops["k_cur"][:, None], ops["v_cur"][:, None],
        positions=q_pos[:, None], valid_len=jnp.ones((B,), jnp.int32),
        past_k=past_k, past_v=past_v, past_len=ops["past_len"],
        window=ops["window"], sink=ops.get("sink"),
        win_k=ops.get("win_k"), win_v=ops.get("win_v"),
        win_len=ops.get("win_len"),
    )[:, 0]


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("dh", [64, 128])
def test_equals_the_general_path(dh, g, scenario):
    ops = _operands(dh, g, **SCENARIOS[scenario])
    want = np.asarray(_oracle(ops))
    got = np.asarray(paged_decode_xla(**ops))
    assert got.shape == (B, KVH * g, dh) and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("t", [1, 3])
def test_chunk_attention_routes_a_single_token_over_a_paged_past(t):
    """``T == 1`` over a paged past takes the new function (whatever
    ``use_pallas`` says, once the kernel's gate has refused); ``T > 1``
    (chunked prefill, verify forwards) still gathers and splits."""
    ops = _operands(64, 4, buf=False, window=6)
    rng = np.random.default_rng(t)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, t, n, 64)), jnp.float32)
        for n in (KVH * 4, KVH, KVH)
    )
    positions = ops["past_len"][:, None] + jnp.arange(t, dtype=jnp.int32)
    common = dict(
        positions=positions, valid_len=jnp.full((B,), t, jnp.int32),
        past_len=ops["past_len"], window=ops["window"],
    )
    past_k, past_v = gather_kv_layer(
        ops["k_pages"], ops["v_pages"], ops["layer"], ops["page_table"], KVH
    )
    want = chunk_attention(q, k, v, past_k=past_k, past_v=past_v, **common)
    before = lowering.xla_decode_count()
    got = chunk_attention(
        q, k, v, past_k_pages=ops["k_pages"], past_v_pages=ops["v_pages"],
        layer=ops["layer"], page_table=ops["page_table"], **common,
    )
    assert lowering.xla_decode_count() - before == (1 if t == 1 else 0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


# ---------------------------------------------------------------------------
# (b) the fused decode window's jaxpr
# ---------------------------------------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


@pytest.mark.parametrize("model", ["tiny-lfm2", "tiny-granite"])
def test_decode_window_gathers_once_and_copies_no_context(model):
    from sutro_tpu.engine.runner import ModelRunner

    mcfg = MODEL_CONFIGS[model]
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, decode_multi_step=4, use_pallas=False,
        param_dtype="bfloat16", activation_dtype="bfloat16",
    )
    runner = ModelRunner(mcfg, ecfg)
    bsz, mp, ps = 4, ecfg.max_pages_per_seq, ecfg.kv_page_size
    steps = ecfg.decode_multi_step

    def window(params, cache, last, past, table, rng, temp, top_p, top_k):
        return runner._window_scan(
            params, cache, last, past, table, rng, temp, top_p, steps, top_k
        )

    before = lowering.xla_decode_count()
    jaxpr = jax.make_jaxpr(window)(
        runner.params, runner.cache, jnp.zeros((bsz,), jnp.int32),
        jnp.zeros((bsz,), jnp.int32), jnp.zeros((bsz, mp), jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((bsz,), jnp.float32),
        jnp.ones((bsz,), jnp.float32), jnp.zeros((bsz,), jnp.int32),
    )
    mixers = lowering.xla_decode_count() - before
    assert mixers >= 1  # every attention mixer traced took the new path

    n_l, n_p, _, kd = runner.cache.k_pages.shape
    ctx = mp * ps
    gathers, wide_concats, f32_contexts = 0, [], []
    # the gathered context in any view of it: pages, rows, split heads
    views = {
        (bsz * mp, ps, kd), (bsz, ctx, kd),
        (bsz, ctx, mcfg.num_kv_heads, mcfg.head_dim),
    }
    for eqn in _eqns(jaxpr.jaxpr):
        shapes_in = [tuple(getattr(v.aval, "shape", ())) for v in eqn.invars]
        if eqn.primitive.name == "gather" and shapes_in[0] == (
            n_l * n_p, ps, kd
        ):
            gathers += 1
        if eqn.primitive.name == "concatenate" and any(
            int(np.prod(s)) >= bsz * ctx * kd for s in shapes_in
        ):
            wide_concats.append(shapes_in)
        for v in eqn.outvars:
            if (
                tuple(v.aval.shape) in views
                and v.aval.dtype == jnp.float32
            ):
                f32_contexts.append((eqn.primitive.name, v.aval.shape))
    assert gathers == 2 * mixers, (gathers, mixers)
    assert not wide_concats, wide_concats
    assert not f32_contexts, f32_contexts


def test_a_model_only_mesh_runs_one_call_a_shard():
    """Under tensor parallelism the fused KV axis is sharded in whole
    KV heads: the runner hands the XLA decode path its mesh, like the
    kernels, and each shard computes its own heads (GSPMD alone would
    all-gather the context for the block-diagonal products). A mesh
    that shards more than ``model`` is left to GSPMD. Same tokens."""
    from sutro_tpu.engine.runner import ModelRunner

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    bsz, mp, ctx, steps = 4, 16, 20, 4
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, 256, ctx).astype(np.int32) for _ in range(bsz)]
    tables = 1 + np.arange(bsz * mp, dtype=np.int32).reshape(bsz, mp)
    tokens, meshes = [], []
    for mesh_kw in (dict(), dict(tp=2), dict(tp=2, dp=2)):
        runner = ModelRunner(
            MODEL_CONFIGS["tiny-dense"],
            EngineConfig(
                kv_page_size=8, max_pages_per_seq=mp, decode_batch_size=bsz,
                max_model_len=128, decode_multi_step=steps, use_pallas=False,
                param_dtype="float32", activation_dtype="float32", **mesh_kw,
            ),
        )
        meshes.append(runner.kernel_mesh)
        runner.prefill_batch(rows, tables)
        toks, _ = runner.decode_multi(
            np.zeros((bsz,), np.int32), np.full((bsz,), ctx, np.int32),
            tables, jax.random.PRNGKey(0), np.zeros((bsz,), np.float32),
            np.ones((bsz,), np.float32), steps,
        )
        tokens.append(np.asarray(toks))
    assert meshes[0] is None and meshes[2] is None
    assert meshes[1] is not None and meshes[1].shape["model"] == 2
    np.testing.assert_array_equal(tokens[1], tokens[0])
    np.testing.assert_array_equal(tokens[2], tokens[0])


# ---------------------------------------------------------------------------
# (c) the count
# ---------------------------------------------------------------------------


def _trace_decode(use_pallas: bool):
    """Trace (never run) one decode step's attention at heads of 64,
    which the kernel's gate refuses."""
    ops = _operands(64, 4, buf=False)
    return jax.make_jaxpr(
        lambda q, k, v: chunk_attention(
            q, k, v,
            positions=ops["past_len"][:, None],
            valid_len=jnp.ones((B,), jnp.int32),
            past_k_pages=ops["k_pages"], past_v_pages=ops["v_pages"],
            layer=ops["layer"], page_table=ops["page_table"],
            past_len=ops["past_len"], use_pallas=use_pallas,
        )
    )(ops["q"][:, None], ops["k_cur"][:, None], ops["v_cur"][:, None])


def test_count_rises_and_the_snapshot_keeps_its_keys():
    snap, count = lowering.snapshot(), lowering.xla_decode_count()
    _trace_decode(use_pallas=False)
    assert lowering.xla_decode_count() == count + 1
    # no kernel was asked for: nothing of the kernels' counts moves,
    # and the new count is no key of the snapshot
    assert lowering.snapshot() == snap
    assert set(snap) == {"paged_decode", "flash_prefill", "kv_write"}
    assert all(
        set(paths) == {"lowered", "interpreted", "reference"}
        for paths in snap.values()
    )


def test_a_refused_kernel_counts_both():
    snap, count = lowering.snapshot(), lowering.xla_decode_count()
    _trace_decode(use_pallas=True)
    after = lowering.snapshot()
    assert lowering.xla_decode_count() == count + 1
    assert (
        after["paged_decode"]["reference"]
        == snap["paged_decode"]["reference"] + 1
    )
    assert after["paged_decode"]["lowered"] == snap["paged_decode"]["lowered"]
    assert (
        after["paged_decode"]["interpreted"]
        == snap["paged_decode"]["interpreted"]
    )


def test_device_report_carries_the_counts():
    from sutro_tpu.engine.runner import device_report

    report = device_report(EngineConfig(use_pallas=False))
    assert report["kernel_paths"] == lowering.snapshot()
    assert report["paged_decode_xla"] == lowering.xla_decode_count()


# ---------------------------------------------------------------------------
# (d) compiled for a described v5e: the granite cell's decode window
# ---------------------------------------------------------------------------

# `temp_size_in_bytes` of the granite cell's `_decode_multi_jit` at batch
# 128 as the parent commit (51cbcca, PR 32) compiles for a described
# v5e:1x1 with this installation (PR 33's builder, no chip attached)
PARENT_TEMP_BYTES = 3_131_333_120


def test_granite_decode_window_keeps_less_scratch_than_the_parent(
    plan, silent_cache
):
    import re

    from sutro_tpu.engine.runner import ModelRunner

    ecfg, arg = plan["ecfg"], plan["arg"]
    bsz, mp = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((bsz,), jnp.int32), arg((bsz,), jnp.int32),
        arg((bsz, mp), jnp.int32), arg((2,), jnp.uint32),
        arg((bsz,), jnp.float32), arg((bsz,), jnp.float32),
        ecfg.decode_multi_step, arg((bsz,), jnp.int32), 1, None,
    )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    # two head-split, padded copies of a layer's context are gone
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.9 * PARENT_TEMP_BYTES, temp
    # and with them every instruction outside a fusion that re-lays,
    # copies or concatenates the gathered context [B, MP*PS, KVH, Dh]
    mcfg = plan["mcfg"]
    ctx = mp * ecfg.kv_page_size
    split = re.compile(
        rf"= \w+\[{bsz},\d+,{mcfg.num_kv_heads},{mcfg.head_dim}\]\S* "
        r"(copy|reshape|concatenate|transpose)\("
    )
    offenders = [
        line.strip()[:160]
        for line in compiled.as_text().splitlines()
        if (m := split.search(line))
        and int(line.split("[")[1].split(",")[1]) >= ctx
    ]
    assert not offenders, offenders
