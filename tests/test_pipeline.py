"""Pipeline parallelism (parallel/pipeline.py) on the 8-way virtual CPU
mesh: GPipe microbatch schedule parity with the plain scanned forward,
PP x TP composition, and runner-level prefill+decode parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.parallel.mesh import make_mesh
from sutro_tpu.parallel.pipeline import (
    pipeline_forward,
    pp_param_shardings,
)


@pytest.mark.slow  # 16-28s/combo of multi-device XLA compiles: full
#                    parity stays pinned by the chunked full-suite run
@pytest.mark.parametrize("model", ["tiny-dense", "tiny-oss"])
@pytest.mark.parametrize("pp,tp,m", [(2, 1, 2), (2, 1, 4), (2, 2, 2)])
def test_pipeline_forward_parity(eight_devices, model, pp, tp, m):
    cfg = MODEL_CONFIGS[model]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, T = 4, 16
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    vl = jnp.asarray([16, 9, 16, 3], jnp.int32)
    ref, _, (k_ref, v_ref) = transformer.forward(cfg, params, ids, pos, vl)

    mesh = make_mesh(1, 1, tp, eight_devices[: pp * tp], pp=pp)
    sharded = jax.device_put(params, pp_param_shardings(params, mesh))
    out, _, (k, v) = pipeline_forward(
        cfg, sharded, ids, pos, vl, mesh, n_microbatches=m
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(k), np.asarray(k_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=2e-4)


def test_pipeline_validates_divisibility(eight_devices):
    cfg = MODEL_CONFIGS["tiny-dense"]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = make_mesh(1, 1, 1, eight_devices[:2], pp=2)
    ids = jnp.zeros((3, 16), jnp.int32)
    pos = jnp.zeros((3, 16), jnp.int32)
    vl = jnp.ones((3,), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_forward(cfg, params, ids, pos, vl, mesh, n_microbatches=2)


def test_pp_runner_generation_matches_single_device(
    eight_devices, mesh_ecfg
):
    """Greedy prefill+decode through the engine runner must be identical
    with the layer stack pipeline-sharded (pp=2) and pp x tp (2x2)."""
    cfg = MODEL_CONFIGS["tiny-dense"]
    prompt = (np.arange(17, dtype=np.int32) * 5) % 199

    def run(mesh):
        runner = ModelRunner(cfg, mesh_ecfg, mesh=mesh)
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
                np.stack([table] + [np.zeros((8,), np.int32)] * 3),
                jax.random.PRNGKey(0),
                np.zeros(4, np.float32),
                np.ones(4, np.float32),
            )
            tok = int(toks[0])
            out.append(tok)
            pos += 1
        return out

    single = run(None)
    assert run(make_mesh(1, 1, 1, eight_devices[:2], pp=2)) == single
    assert run(make_mesh(1, 1, 2, eight_devices[:4], pp=2)) == single


def test_pp_decode_stage_local_memory(eight_devices, mesh_ecfg):
    """Under pp=2 each device holds exactly 1/2 of every layer-stacked
    param leaf and 1/2 of the KV page pool — PP actually reduces decode
    residency (decode runs pipeline_decode, not a GSPMD all-gather)."""
    cfg = MODEL_CONFIGS["tiny-dense"]
    mesh = make_mesh(1, 1, 1, eight_devices[:2], pp=2)
    runner = ModelRunner(cfg, mesh_ecfg, mesh=mesh)
    wq = runner.params["layers"]["wq"]
    assert wq.sharding.spec[0] == "pipe"
    assert wq.addressable_shards[0].data.nbytes == wq.nbytes // 2
    kp = runner.cache.k_pages
    assert kp.addressable_shards[0].data.nbytes == kp.nbytes // 2
