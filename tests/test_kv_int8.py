"""Int8 KV cache with per-token scales (EngineConfig.kv_quantize,
VERDICT r3 next-step 4): write_kv quantizes at the single write choke
point, the gather fallback and the Pallas paged kernel dequantize, and
the engine runs end-to-end with the quantized pool. Halves decode HBM
traffic and doubles page capacity; parity is numeric (int8 error), the
kernel-vs-fallback comparison is tight (identical quantized values)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import (
    alloc_cache,
    gather_kv_layer,
    write_kv,
)
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops.attention import chunk_attention
from sutro_tpu.ops.pallas_paged import paged_decode_attention

# pools are stacked [L, NP, PS, KVH*Dh] and readers index a MIDDLE layer
N_LAYERS = 3
LAYER = jnp.asarray(1, jnp.int32)


def _ecfg(**kw):
    base = dict(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", kv_quantize="int8",
    )
    base.update(kw)
    return EngineConfig(**base)


def test_write_then_gather_roundtrip_error_bound():
    """Quantize-dequantize error is bounded by half a step of each
    token's scale (amax/127)."""
    mcfg = MODEL_CONFIGS["tiny-dense"]
    ecfg = _ecfg()
    cache = alloc_cache(mcfg, ecfg, num_pages=9)
    L = mcfg.num_layers
    KVH, Dh = mcfg.num_kv_heads, mcfg.head_dim
    B, T = 2, 11
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((L, B, T, KVH, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, B, T, KVH, Dh)), jnp.float32)
    table = np.zeros((B, ecfg.max_pages_per_seq), np.int32)
    table[0, :2] = [1, 2]
    table[1, :2] = [3, 4]
    cache = write_kv(
        cache, k, v, jnp.asarray(table),
        jnp.zeros((B,), jnp.int32), jnp.full((B,), T, jnp.int32),
    )
    gk, gv = gather_kv_layer(
        cache.k_pages, cache.v_pages, jnp.asarray(0, jnp.int32),
        jnp.asarray(table), KVH,
        k_scale=cache.k_scale, v_scale=cache.v_scale,
    )
    got = np.asarray(gk)[:, :T].reshape(B, T, KVH, Dh)
    want = np.asarray(k[0])
    tol = np.abs(want).reshape(B, T, -1).max(-1) / 127.0 * 0.5 + 1e-6
    assert (np.abs(got - want).reshape(B, T, -1).max(-1) <= tol).all()
    gotv = np.asarray(gv)[:, :T].reshape(B, T, KVH, Dh)
    wantv = np.asarray(v[0])
    tolv = np.abs(wantv).reshape(B, T, -1).max(-1) / 127.0 * 0.5 + 1e-6
    assert (np.abs(gotv - wantv).reshape(B, T, -1).max(-1) <= tolv).all()


def _quantized_case(rng, *, B=3, NH=4, KVH=2, Dh=16, PS=8, MP=6, NP=32):
    from sutro_tpu.engine.kvcache import _quantize_tokens

    q = jnp.asarray(rng.standard_normal((B, 1, NH, Dh)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    pool = (N_LAYERS, NP, PS, KVH * Dh)
    kf = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    vf = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    kq, ks = _quantize_tokens(kf)
    vq, vs = _quantize_tokens(vf)
    table = np.zeros((B, MP), np.int32)
    next_p = 1
    for b in range(B):
        table[b] = np.arange(next_p, next_p + MP)
        next_p += MP
    past_len = jnp.asarray(rng.integers(1, MP * PS, B), jnp.int32)
    return q, k_cur, v_cur, kq, ks, vq, vs, jnp.asarray(table), past_len


@pytest.mark.parametrize("window", [0, 5])
def test_paged_kernel_int8_matches_dequant_reference(window):
    """The Pallas kernel's in-kernel dequant (score/probability scaling
    per page slice) matches the XLA gather-dequant fallback over the
    SAME quantized values — tight tolerance, no quantization slack."""
    rng = np.random.default_rng(7)
    q, k_cur, v_cur, kq, ks, vq, vs, table, past_len = _quantized_case(rng)
    B = q.shape[0]
    win = jnp.asarray(window, jnp.int32)

    ref = chunk_attention(
        q, k_cur, v_cur,
        positions=past_len[:, None],
        valid_len=jnp.ones((B,), jnp.int32),
        past_k_pages=kq, past_v_pages=vq, layer=LAYER,
        past_k_scale=ks, past_v_scale=vs,
        page_table=table, past_len=past_len, window=win,
        use_pallas=False,
    )
    got = paged_decode_attention(
        q[:, 0], kq, vq, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        win, None, interpret=True, k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("group_pages", [2, 4])
def test_paged_kernel_int8_chunked(group_pages, paged_ring):
    """Groups of several pages with scale DMAs, in a ring that wraps:
    per-page scale slices still land on the right score columns."""
    paged_ring(2 * group_pages, group_pages)
    rng = np.random.default_rng(11)
    MP = 6
    q, k_cur, v_cur, kq, ks, vq, vs, table, past_len = _quantized_case(
        rng, MP=MP, NP=40
    )
    B = q.shape[0]
    win = jnp.asarray(0, jnp.int32)
    ref = chunk_attention(
        q, k_cur, v_cur,
        positions=past_len[:, None],
        valid_len=jnp.ones((B,), jnp.int32),
        past_k_pages=kq, past_v_pages=vq, layer=LAYER,
        past_k_scale=ks, past_v_scale=vs,
        page_table=table, past_len=past_len, window=win,
        use_pallas=False,
    )
    got = paged_decode_attention(
        q[:, 0], kq, vq, LAYER, table, past_len, k_cur[:, 0], v_cur[:, 0],
        win, None, interpret=True, k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), atol=2e-5, rtol=2e-5
    )


def test_decode_attention_close_to_unquantized():
    """End-to-end numeric sanity: attention over an int8 cache is close
    to attention over the exact cache (int8 error only)."""
    from sutro_tpu.engine.kvcache import _quantize_tokens

    rng = np.random.default_rng(3)
    B, NH, KVH, Dh, PS, MP, NP = 2, 4, 2, 16, 8, 4, 16
    q = jnp.asarray(rng.standard_normal((B, 1, NH, Dh)), jnp.float32)
    k_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    v_cur = jnp.asarray(rng.standard_normal((B, 1, KVH, Dh)), jnp.float32)
    pool = (N_LAYERS, NP, PS, KVH * Dh)
    kf = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    vf = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    kq, ks = _quantize_tokens(kf)
    vq, vs = _quantize_tokens(vf)
    table = jnp.asarray(
        np.arange(1, 1 + B * MP, dtype=np.int32).reshape(B, MP)
    )
    past_len = jnp.asarray([MP * PS - 3, 7], jnp.int32)
    kw = dict(
        positions=past_len[:, None],
        valid_len=jnp.ones((B,), jnp.int32),
        layer=LAYER, page_table=table, past_len=past_len,
        window=jnp.asarray(0, jnp.int32), use_pallas=False,
    )
    exact = chunk_attention(
        q, k_cur, v_cur, past_k_pages=kf, past_v_pages=vf, **kw
    )
    quant = chunk_attention(
        q, k_cur, v_cur, past_k_pages=kq, past_v_pages=vq,
        past_k_scale=ks, past_v_scale=vs, **kw
    )
    np.testing.assert_allclose(
        np.asarray(quant), np.asarray(exact), atol=0.05, rtol=0.05
    )


def test_engine_end_to_end_int8_kv(byte_tok):
    """Full scheduler job over the quantized pool: every row completes
    with sane outputs, prefix cache and windows included."""
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest

    runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], _ecfg())
    assert runner.cache.quantized
    b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
    prefix = "SHARED SYSTEM PROMPT FOR EVERY ROW OF THIS JOB: "
    reqs = [
        GenRequest(
            row_id=i,
            prompt_ids=np.array(
                byte_tok.encode(prefix + f"item {i}"), np.int32
            ),
            max_new_tokens=8,
            temperature=0.0,
        )
        for i in range(6)
    ]
    res = {}
    out = b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
    assert out == "completed"
    assert set(res) == set(range(6))
    for r in res.values():
        assert r.finish_reason in ("stop", "length")
        assert np.isfinite(r.cumulative_logprob)
    # greedy outputs should largely agree with the exact-cache engine
    # (tiny f32 model, small quantization error) — require majority
    # token agreement, not equality
    runner2 = ModelRunner(
        MODEL_CONFIGS["tiny-dense"], _ecfg(kv_quantize=None)
    )
    b2 = ContinuousBatcher(runner2, stop_ids=byte_tok.stop_ids())
    reqs2 = [
        GenRequest(
            row_id=i,
            prompt_ids=np.array(
                byte_tok.encode(prefix + f"item {i}"), np.int32
            ),
            max_new_tokens=8,
            temperature=0.0,
        )
        for i in range(6)
    ]
    res2 = {}
    b2.run(reqs2, on_result=lambda r: res2.__setitem__(r.row_id, r))
    agree = sum(
        t1 == t2
        for i in res
        for t1, t2 in zip(res[i].token_ids, res2[i].token_ids)
    )
    total = sum(len(res2[i].token_ids) for i in res2)
    assert agree >= total * 0.5, f"{agree}/{total} tokens agree"


def test_int8_kv_under_tp_mesh_matches_single_device(eight_devices):
    """int8 KV under a dp x tp mesh: per-token scales are computed over
    the FULL fused KD axis (a cross-shard reduce under GSPMD), so they
    are shard-invariant and the scale pools replicate — greedy
    generation must match the single-device int8 cache exactly."""
    import jax

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.parallel.mesh import make_mesh

    cfg = MODEL_CONFIGS["tiny-dense"]
    prompt = np.arange(11, dtype=np.int32) % 200

    def run(mesh):
        runner = ModelRunner(cfg, _ecfg(), mesh=mesh)
        assert runner.ecfg.kv_quantize == "int8", "gate must not strip"
        assert runner.cache.quantized
        table = np.zeros((16,), np.int32)
        table[:4] = [1, 2, 3, 4]
        logits = runner.prefill(prompt, table)
        tok = int(np.argmax(logits))
        out = [tok]
        pos = len(prompt)
        for _ in range(4):
            toks, _ = runner.decode_step(
                np.array([tok, 0, 0, 0], np.int32),
                np.array([pos, 0, 0, 0], np.int32),
                np.stack([table] + [np.zeros((16,), np.int32)] * 3),
                jax.random.PRNGKey(0),
                np.zeros(4, np.float32),
                np.ones(4, np.float32),
            )
            tok = int(toks[0])
            out.append(tok)
            pos += 1
        return out

    single = run(None)
    sharded = run(make_mesh(2, 1, 2, eight_devices[:4]))
    assert single == sharded


def test_int8_kv_under_pp_mesh_warns_and_strips(eight_devices):
    """Pipeline decode carries bare page pools (no scales): the gate
    must warn and fall back to the bf16 cache under pp only."""
    import warnings

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.parallel.mesh import make_mesh

    cfg = MODEL_CONFIGS["tiny-dense"]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        runner = ModelRunner(
            cfg, _ecfg(),
            mesh=make_mesh(1, 1, 2, eight_devices[:4], pp=2),
        )
    assert runner.ecfg.kv_quantize is None
    assert not runner.cache.quantized
    assert any("pipeline" in str(x.message) for x in w)
