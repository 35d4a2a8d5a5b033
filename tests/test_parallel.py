"""Multi-device sharding tests on the 8-way virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8 — SURVEY §4's multi-device CI
strategy). Verifies TP/EP/DP shardings produce the same results as
single-device execution."""

import jax
import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.parallel.mesh import make_mesh, mesh_shape
from sutro_tpu.parallel.sharding import param_shardings, shard_params


def _ecfg(**kw):
    base = dict(
        kv_page_size=8, max_pages_per_seq=8, decode_batch_size=4,
        max_model_len=64, use_pallas=False, param_dtype="float32",
    )
    base.update(kw)
    return EngineConfig(**base)


def test_mesh_construction(eight_devices):
    mesh = make_mesh(2, 2, 2, eight_devices)
    assert mesh_shape(mesh) == (2, 1, 1, 2, 2)
    with pytest.raises(ValueError, match="exceeds"):
        make_mesh(4, 4, 4, eight_devices)
    with pytest.raises(ValueError, match="exceeds"):
        make_mesh(2, 2, 2, eight_devices, sp=2)
    with pytest.raises(ValueError, match="exceeds"):
        make_mesh(2, 2, 2, eight_devices, pp=2)


def test_param_shardings_cover_all_leaves(eight_devices):
    from sutro_tpu.models import transformer

    mesh = make_mesh(1, 2, 4, eight_devices)
    for name in ("tiny-moe", "tiny-oss"):
        cfg = MODEL_CONFIGS[name]
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        sh = param_shardings(params, mesh)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_s = jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: hasattr(x, "spec")
        )
        assert len(flat_p) == len(flat_s)


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
def test_tp_matches_single_device_generation(eight_devices):
    """Greedy generation must be identical under TP+EP sharding."""
    cfg = MODEL_CONFIGS["tiny-moe"]
    prompt = np.arange(11, dtype=np.int32) % 200

    def run(mesh):
        runner = ModelRunner(cfg, _ecfg(), mesh=mesh)
        table = np.zeros((8,), np.int32)
        table[:4] = [1, 2, 3, 4]
        logits = runner.prefill(prompt, table)
        tok = int(np.argmax(logits))
        out = [tok]
        pos = len(prompt)
        for _ in range(4):
            toks, _ = runner.decode_step(
                np.array([tok, 0, 0, 0], np.int32),
                np.array([pos, 0, 0, 0], np.int32),
                np.stack([table] + [np.zeros_like(table)] * 3),
                jax.random.PRNGKey(0),
                np.zeros(4, np.float32),
                np.ones(4, np.float32),
            )
            tok = int(toks[0])
            out.append(tok)
            pos += 1
        return out

    single = run(None)
    sharded = run(make_mesh(1, 2, 2, eight_devices[:4]))
    assert single == sharded


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
def test_dp_ep_tp_full_mesh_step(eight_devices):
    """A full 2x2x2 mesh executes a prefill+decode step without error and
    params actually land sharded."""
    cfg = MODEL_CONFIGS["tiny-moe"]
    mesh = make_mesh(2, 2, 2, eight_devices)
    runner = ModelRunner(cfg, _ecfg(), mesh=mesh)
    wq = runner.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 8
    table = np.zeros((8,), np.int32)
    table[:2] = [1, 2]
    logits = runner.prefill(np.arange(5, dtype=np.int32), table)
    assert logits.shape == (cfg.vocab_size,)
    assert np.isfinite(np.asarray(logits)).all()


def test_shard_params_helper(eight_devices):
    from sutro_tpu.models import transformer

    mesh = make_mesh(1, 1, 8, eight_devices)
    cfg = MODEL_CONFIGS["tiny-dense"]  # NHD=128 divides by 8
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_params(params, mesh)
    assert len(sharded["layers"]["wq"].sharding.device_set) == 8
    # norms replicated
    assert sharded["layers"]["attn_norm"].sharding.is_fully_replicated


# ---------------------------------------------------------------------------
# Explicit expert parallelism (ops/moe_ep.py)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
@pytest.mark.parametrize("dp,ep,tp", [(2, 2, 2), (1, 4, 2), (1, 2, 1)])
@pytest.mark.parametrize("with_bias", [False, True])
def test_moe_ep_matches_reference(eight_devices, dp, ep, tp, with_bias):
    """The shard_map EP path (local grouped GEMMs + one psum) must
    reproduce the single-device MoE exactly — no token drops, biases
    and gpt-oss activation included."""
    import jax.numpy as jnp

    from sutro_tpu.ops.moe import moe_mlp
    from sutro_tpu.ops.moe_ep import moe_mlp_ep

    rng = np.random.default_rng(3)
    B, T, H, F, E, K = 2, 3, 16, 32, 4, 2
    act = "swiglu_oss" if with_bias else "silu"
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x = f32(B, T, H)
    router = f32(H, E)
    wg, wu = f32(E, H, F), f32(E, H, F)
    wd = f32(E, F, H)
    kw = dict(top_k=K, activation=act)
    if with_bias:
        kw.update(
            router_b=f32(E),
            bias_gate=f32(E, F) * 0.1,
            bias_up=f32(E, F) * 0.1,
            bias_down=f32(E, H) * 0.1,
        )

    want = moe_mlp(x, router, wg, wu, wd, method="dense", **kw)
    mesh = make_mesh(dp, ep, tp, eight_devices)
    got = jax.jit(
        lambda *a: moe_mlp_ep(*a, mesh=mesh, **kw)
    )(x, router, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_moe_ep_odd_batch_replicates(eight_devices):
    """B not divisible by dp falls back to replicated tokens (still
    exact)."""
    import jax.numpy as jnp

    from sutro_tpu.ops.moe import moe_mlp
    from sutro_tpu.ops.moe_ep import moe_mlp_ep

    rng = np.random.default_rng(5)
    B, T, H, F, E, K = 3, 2, 8, 16, 4, 2
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, router = f32(B, T, H), f32(H, E)
    wg, wu, wd = f32(E, H, F), f32(E, H, F), f32(E, F, H)
    mesh = make_mesh(2, 2, 2, eight_devices)
    want = moe_mlp(x, router, wg, wu, wd, top_k=K, method="dense")
    got = jax.jit(
        lambda *a: moe_mlp_ep(*a, mesh=mesh, top_k=K)
    )(x, router, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_moe_ep_weight_residency(eight_devices):
    """With the sharding rules applied, each device holds exactly
    1/(ep*tp) of the expert weights — the reason this path exists
    (no GSPMD all-gather of expert weights)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(1, 4, 2, eight_devices)
    E, H, F = 8, 16, 64
    w = jnp.ones((E, H, F), jnp.float32)
    w = jax.device_put(
        w, NamedSharding(mesh, P("expert", None, "model"))
    )
    shard = w.addressable_shards[0].data
    assert shard.shape == (E // 4, H, F // 2)


@pytest.mark.slow  # multi-device XLA compiles: excluded from the
#   single-process tier-1 run (in-process compile accumulation is
#   what trips this host's XLA:CPU flake, see run_tests_chunked.sh);
#   the chunked full-suite CI runs it per-file
@pytest.mark.parametrize("sp,pp", [(2, 1), (1, 2)])
def test_moe_ep_gspmd_fallback_under_sp_pp(eight_devices, sp, pp):
    """VERDICT r3 weak #6: under sp/pp the explicit shard_map EP path
    falls back to GSPMD MoE (runner.ep_mesh is None — shard_map nesting
    is unsupported). The fallback COMBINATION must still generate
    greedy tokens identical to single-device; its perf remains
    chip-gated (PARITY.md), but correctness is pinned here."""
    cfg = MODEL_CONFIGS["tiny-moe"]
    prompt = np.arange(11, dtype=np.int32) % 200

    def run(mesh):
        runner = ModelRunner(cfg, _ecfg(), mesh=mesh)
        if mesh is not None:
            assert runner.ep_mesh is None, (
                "explicit EP must sit out under sp/pp"
            )
        table = np.zeros((8,), np.int32)
        table[:4] = [1, 2, 3, 4]
        logits = runner.prefill(prompt, table)
        tok = int(np.argmax(logits))
        out = [tok]
        pos = len(prompt)
        for _ in range(3):
            toks, _ = runner.decode_step(
                np.array([tok, 0, 0, 0], np.int32),
                np.array([pos, 0, 0, 0], np.int32),
                np.stack([table] + [np.zeros_like(table)] * 3),
                jax.random.PRNGKey(0),
                np.zeros(4, np.float32),
                np.ones(4, np.float32),
            )
            tok = int(toks[0])
            out.append(tok)
            pos += 1
        return out

    single = run(None)
    sharded = run(
        make_mesh(1, 2, 2, eight_devices, sp=sp, pp=pp)
    )
    assert single == sharded


def test_tp_pallas_kernels_match_single_device_xla(
    eight_devices, monkeypatch
):
    """XLA cannot partition a Mosaic call, so under a tp mesh the
    Pallas kernels run as a shard_map over ``model`` (ops/lowering.py
    shard_over_model). Greedy prefill + single-step + fused-window
    decode through the sharded runner (kernels in interpret mode on
    CPU) must match the single-device XLA path — and the kernels must
    actually have been traced, not bypassed."""
    from sutro_tpu.ops import lowering
    from tests.test_prefix_split import _force_interpret

    _force_interpret(monkeypatch)
    cfg = MODEL_CONFIGS["tiny-dense"]  # KVH=2 divides tp=2
    prompt = (np.arange(13, dtype=np.int32) * 7) % 200

    def run(mesh, use_pallas):
        runner = ModelRunner(
            cfg, _ecfg(use_pallas=use_pallas, decode_multi_step=3),
            mesh=mesh,
        )
        assert runner.use_pallas is use_pallas
        table = np.zeros((8,), np.int32)
        table[:4] = [1, 2, 3, 4]
        tables = np.stack([table] + [np.zeros_like(table)] * 3)
        tok = int(np.argmax(runner.prefill(prompt, table)))
        out = [tok]
        pos = len(prompt)
        zeros, ones = np.zeros(4, np.float32), np.ones(4, np.float32)
        toks, _ = runner.decode_step(
            np.array([tok, 0, 0, 0], np.int32),
            np.array([pos, 0, 0, 0], np.int32),
            tables, jax.random.PRNGKey(0), zeros, ones,
        )
        out.append(int(toks[0]))
        win, _ = runner.decode_multi(
            np.array([out[-1], 0, 0, 0], np.int32),
            np.array([pos + 1, 0, 0, 0], np.int32),
            tables, jax.random.PRNGKey(1), zeros, ones, 3,
        )
        return out + [int(t) for t in win[:, 0]]

    before = lowering.snapshot()
    sharded = run(make_mesh(1, 1, 2, eight_devices[:2]), True)
    after = lowering.snapshot()
    for kernel in ("paged_decode", "kv_write"):
        assert (
            after[kernel]["interpreted"] > before[kernel]["interpreted"]
        ), kernel
    assert sharded == run(None, False)


def test_pallas_kernels_refuse_meshes_they_are_not_partitioned_for(
    eight_devices,
):
    """The kernels shard over ``model`` only: a mesh that shards another
    axis takes the XLA path when Pallas is on auto, and asking for the
    kernels there raises at construction, not at the first compile."""
    from sutro_tpu.engine.runner import resolve_pallas

    mesh = make_mesh(2, 1, 2, eight_devices[:4])
    use, why = resolve_pallas(_ecfg(use_pallas=None), mesh)
    assert use is False and "cpu" in why
    with pytest.raises(ValueError, match="'model' axis only"):
        resolve_pallas(_ecfg(use_pallas=True), mesh)
    assert resolve_pallas(
        _ecfg(use_pallas=True), make_mesh(1, 1, 2, eight_devices[:2])
    )[0]


def test_flash_prefill_shards_over_model_axis(eight_devices):
    """Flash prefill is independent per KV head, so its shard_map over
    ``model`` (chunk_attention's kernel_mesh) must equal the unsharded
    kernel — heads split in whole-KV-head blocks."""
    import functools

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from sutro_tpu.ops import attention, lowering
    from sutro_tpu.ops.pallas_flash import flash_prefill

    B, T, NH, KVH, Dh = 1, 128, 4, 2, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, T, NH, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, KVH, Dh)), jnp.float32)
    flash = functools.partial(flash_prefill, interpret=True)
    want = flash(q, k, v)
    got = lowering.shard_over_model(
        make_mesh(1, 1, 2, eight_devices[:2]), flash,
        dict(q=q, k=k, v=v), attention._FLASH_SPECS,
        P(None, None, "model", None),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
