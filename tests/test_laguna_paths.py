"""Which paths a model whose layer kinds differ in their query heads takes
and which it refuses, by name: the presets and the ONE place a kind's
head count and rotary width are read; short and long rows in one queue
through the scheduler, with the counts of a prefill's padding; the
attention kernels at 6 and at 9 query heads a KV head, interpreted, and
their traces by head count; the routed layer a tile of tokens at a time;
a K/V write longer than one call takes; the pools' division when the
device is short; and what is not built, refused."""

import dataclasses
import functools
import json
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import laguna_moe
from sutro_tpu import telemetry
from sutro_tpu.engine import kvcache
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.prefixstore import PrefixStore
from sutro_tpu.engine.runner import ModelRunner, device_report
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import lowering, moe, pallas_chunk, pallas_flash, pallas_paged
from sutro_tpu.ops.attention import chunk_attention

MCFG = MODEL_CONFIGS["tiny-laguna"]
CUT = MODEL_CONFIGS["laguna-s-2.1-l9-ep8"]
WHOLE = MODEL_CONFIGS["laguna-s-2.1"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-laguna-cpu.json").read_text()
)
PS, MP, B = 4, 32, 4
TOK = ByteTokenizer(vocab_size=MCFG.vocab_size)


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=B,
        max_model_len=PS * MP, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=PS * MP, seed=9,
        decode_multi_step=4, prefill_batch_size=4,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def two_pools():
    first = ModelRunner(MCFG, engine(), num_pages=1 + B * MP)
    r = ModelRunner(
        MCFG, engine(), params=first.params, num_pages=1 + B * MP,
        window_pages=1 + B * first.window_span,
    )
    assert r.pools.window is not None
    return r


@pytest.fixture(autouse=True)
def _telemetry_on():
    before = telemetry.ENABLED
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(before)


def reqs(prompts, new=8):
    return [
        GenRequest(row_id=i, prompt_ids=np.array(TOK.encode(p), np.int32),
                   max_new_tokens=new, temperature=0.0)
        for i, p in enumerate(prompts)
    ]


def run(b, rs):
    out = {}
    b.run(rs, on_result=lambda r: out.__setitem__(r.row_id, r))
    return {i: list(r.token_ids) for i, r in sorted(out.items())}


def counter(name, label=""):
    series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
    return sum(v for k, v in series.items() if label in str(k))


# -- the presets ------------------------------------------------------------


def test_the_presets_are_the_published_lists_and_one_place_says_a_kinds_heads():
    assert WHOLE.num_layers == 48 and CUT.num_layers == 9
    assert WHOLE.mixers.count("attention") == 12
    assert WHOLE.mixers.count("swa") == 36
    assert WHOLE.mixers[:5] == ("attention", "swa", "swa", "swa", "attention")
    assert WHOLE.ffns == ("dense",) + ("moe",) * 47
    for cfg in (WHOLE, CUT):
        assert (cfg.heads_of("attention"), cfg.heads_of("swa")) == (48, 72)
        assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (48, 8, 128)
        assert (cfg.rotary_dim_of("attention"), cfg.rotary_dim_of("swa")) == (
            64, 128)
        assert (cfg.rope_theta, cfg.local_rope_theta) == (500_000.0, 10_000.0)
        assert cfg.rope_attention_factor == 1.4852030263919618
        assert (cfg.attn_gate, cfg.moe_shared_gate) == ("head", True)
        assert (cfg.moe_experts, cfg.moe_top_k, cfg.router_scale) == (
            256, 10, 2.5)
        assert cfg.window_array()[:5] == (0, 512, 512, 512, 0)
    assert (CUT.experts_held, CUT.vocab_size) == (32, 100_352 // 8)
    # the cell's layers: the dense layer alone, then two whole periods
    assert transformer.layer_groups(CUT) == [(0, 1, 1), (1, 4, 2)]
    assert (MCFG.heads_of("attention"), MCFG.heads_of("swa")) == (4, 6)
    # Solar's gate a channel is the same field at another value
    assert MODEL_CONFIGS["solar-open2-250b"].attn_gate == "channel"
    assert MODEL_CONFIGS["mellum2-12b-a2.5b"].attn_gate == ""


def test_every_shape_of_a_kinds_layers_follows_its_heads():
    shapes = jax.eval_shape(
        functools.partial(transformer.init_params, CUT, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0),
    )["layers"]
    assert shapes["swa"]["wq"].shape == (6, 3072, 72 * 128)
    assert shapes["swa"]["wo"].shape == (6, 72 * 128, 3072)
    assert shapes["swa"]["w_attn_gate"].shape == (6, 3072, 72)
    assert shapes["attn"]["wq"].shape == (3, 3072, 48 * 128)
    assert shapes["attn"]["w_attn_gate"].shape == (3, 3072, 48)
    # both kinds keep 8 KV heads: one page width, one K/V stack
    assert shapes["swa"]["wk"].shape[1:] == shapes["attn"]["wk"].shape[1:] == (
        3072, 1024)
    assert shapes["moe"]["shared_expert_gate"].shape == (8, 3072, 1)
    assert shapes["moe"]["we_up"].shape == (8, 32, 3072, 1024)
    assert shapes["moe"]["router"].shape == (8, 3072, 256)


# -- through the scheduler: lengths mixed in one queue ------------------------


def test_short_and_long_rows_in_one_queue_are_the_references(two_pools):
    prompts = [
        "ab", "a long row " * 7, "mid-sized row here", "x" * 60, "tail",
        "another long one, " * 4,
    ]
    real0 = counter("sutro_prefill_tokens_total", "real")
    pad0 = counter("sutro_prefill_tokens_total", "padded")
    started = time.monotonic() - telemetry.RECORDER.epoch_mono
    b = ContinuousBatcher(two_pools, stop_ids=[])
    got = run(b, reqs(prompts, new=10))
    for i, p in enumerate(prompts):
        ids = TOK.encode(p) + got[i]
        want = np.asarray(laguna_moe.logits_at(
            KEYS, two_pools.params, ids[:-1],
            list(range(len(ids) - 11, len(ids) - 1)),
        ))
        assert got[i] == [int(t) for t in want.argmax(-1)]
    assert two_pools.pools.window.released_total > 0
    # the counter: the rows' own tokens, and what their buckets hold
    # beyond them; the spans say each dispatch's rows, bucket and tokens
    real = counter("sutro_prefill_tokens_total", "real") - real0
    padded = counter("sutro_prefill_tokens_total", "padded") - pad0
    assert real == sum(len(TOK.encode(p)) for p in prompts)
    spans = [
        s for s in telemetry.RECORDER.snapshot()
        if s["name"] == "prefill" and s["t0_s"] >= started
        and "bucket" in (s.get("attrs") or {})
    ]
    assert sum(s["attrs"]["real_tokens"] for s in spans) == real
    cells = sum(np.prod(s["attrs"]["bucket"]) for s in spans)
    assert cells - real == padded and padded > real     # 77 beside 2
    assert any(s["attrs"]["rows"] > 1 for s in spans)
    windows = [
        s["attrs"] for s in telemetry.RECORDER.snapshot()
        if s["name"] == "decode_window" and s["t0_s"] >= started
    ]
    # the mean rows a full and a window layer read, by kind
    assert all(
        a["kv_tokens_window"] <= min(a["kv_tokens_full"], MCFG.sliding_window)
        for a in windows
    ) and windows


def test_rows_that_share_a_prefix_prefill_it_again_and_say_so(two_pools):
    shell = "one shared shell of twenty-odd bytes, then: "
    prompts = [shell + t for t in ("alpha", "beta beta", "gamma")]
    before = counter(
        "sutro_state_fallback_prefill_tokens_total",
        "prefix_without_window_pages",
    )
    b = ContinuousBatcher(two_pools, stop_ids=[], prefix_store=PrefixStore(PS))
    assert b._prefix_store is None        # a shared page has no window page
    run(b, reqs(prompts))
    shared = (len(TOK.encode(shell)) // PS) * PS
    assert counter(
        "sutro_state_fallback_prefill_tokens_total",
        "prefix_without_window_pages",
    ) - before == 2 * shared


# -- what is not built --------------------------------------------------------


def test_what_is_not_built_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="no int8 scale pools"):
        ModelRunner(MCFG, engine(kv_quantize="int8"), num_pages=9)
    with pytest.raises(NotImplementedError, match="several kinds"):
        ModelRunner(MCFG, engine(quantize="int8"), num_pages=9)
    if jax.device_count() >= 2:
        with pytest.raises(NotImplementedError, match="moe_experts_held"):
            ModelRunner(MCFG, engine(tp=2), num_pages=9)
    r = types.SimpleNamespace(refusals=kvcache.cache_layout(
        MCFG, engine(), 1 + B * MP, jnp.float32, None, 1 + B * 4,
    ).refusals)
    assert "window" in r.refusals["share"] and "window" in r.refusals["tiers"]
    check = transformer._check_mixed
    check(MCFG)
    bad = dataclasses.replace
    with pytest.raises(ValueError, match="no multiple of 2 KV heads"):
        check(bad(MCFG, window_num_heads=5))
    with pytest.raises(ValueError, match="rotary part of 7"):
        check(bad(MCFG, rotary_dim=7))
    with pytest.raises(ValueError, match="rotary part of 32"):
        check(bad(MCFG, rotary_dim=32))
    with pytest.raises(ValueError, match="attn_gate 'row'"):
        check(bad(MCFG, attn_gate="row"))
    with pytest.raises(NotImplementedError, match="narrower than the head"):
        check(bad(MCFG, position_embedding="nope"))
    with pytest.raises(ValueError, match="moe_shared_gate"):
        check(bad(MCFG, moe_shared_intermediate_size=0))
    with pytest.raises(ValueError, match="a swa layer's"):
        check(bad(MCFG, layer_types=("attention",) * 9, sliding_window=0))
    # the one scan of a homogeneous model builds none of the four
    for field in (dict(window_num_heads=6), dict(rotary_dim=8),
                  dict(attn_gate="head")):
        with pytest.raises(NotImplementedError, match="layer_types"):
            transformer._init_params(
                bad(MODEL_CONFIGS["tiny-dense"], name="a dense model",
                    **field),
                jax.random.PRNGKey(0), jnp.float32,
            )
    # a laguna checkpoint is refused, not loaded as another family's
    from sutro_tpu.engine import weights

    with pytest.raises(NotImplementedError):
        weights._load_mixed(MCFG, lambda *a, **k: None, jnp.float32)


# -- the kernels at 6 and at 9 query heads a KV head ---------------------------


@pytest.mark.parametrize("G", [6, 9])
def test_the_attention_kernels_take_six_and_nine_heads_a_kv_head(G):
    """Interpreted, at heads of 128 over 2 KV heads, against the XLA
    forms: one decode step over pages (with a window that starts inside
    them), a prefill, a chunk over a paged past; and each trace is
    counted under its query heads."""
    rng = np.random.default_rng([61, G])
    KVH, Dh, PSk, MPk, L = 2, 128, 8, 6, 2
    NH, KD = KVH * G, KVH * Dh
    f32 = jnp.float32
    before = lowering.kernel_heads_counts()

    def grew(kernel):
        key = f"{kernel}@{NH}"
        now = lowering.kernel_heads_counts()
        return now[key]["interpreted"] - before.get(key, {}).get(
            "interpreted", 0)

    layer = jnp.asarray(1, jnp.int32)
    kp = jnp.asarray(rng.standard_normal((L, 1 + 3 * MPk, PSk, KD)), f32)
    vp = jnp.asarray(rng.standard_normal((L, 1 + 3 * MPk, PSk, KD)), f32)
    table = jnp.asarray(
        1 + rng.permutation(3 * MPk).reshape(3, MPk).astype(np.int32))
    past = jnp.asarray([5, 29, 48], jnp.int32)
    for window in (0, 11):
        win = jnp.asarray(window, jnp.int32)
        q = jnp.asarray(rng.standard_normal((3, 1, NH, Dh)), f32)
        k = jnp.asarray(rng.standard_normal((3, 1, KVH, Dh)), f32)
        v = jnp.asarray(rng.standard_normal((3, 1, KVH, Dh)), f32)
        want = chunk_attention(
            q, k, v, positions=past[:, None], valid_len=jnp.ones(3, jnp.int32),
            past_k_pages=kp, past_v_pages=vp, layer=layer, page_table=table,
            past_len=past, window=win, use_pallas=False,
        )
        got = pallas_paged.paged_decode_attention(
            q[:, 0], kp, vp, layer, table, past, k[:, 0], v[:, 0], win,
            interpret=True,
        )
        np.testing.assert_allclose(got, want[:, 0], atol=2e-5, rtol=2e-5)
    assert grew("paged_decode") >= 1

    T = 256
    q = jnp.asarray(rng.standard_normal((1, T, NH, Dh)), f32)
    k = jnp.asarray(rng.standard_normal((1, T, KVH, Dh)), f32)
    v = jnp.asarray(rng.standard_normal((1, T, KVH, Dh)), f32)
    assert pallas_flash.flash_prefill_supported(q, k, None, None)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    for window in (0, 40):
        win = jnp.asarray(window, jnp.int32)
        want = chunk_attention(
            q, k, v, positions=pos, valid_len=jnp.asarray([T], jnp.int32),
            window=win, use_pallas=False,
        )
        got = pallas_flash.flash_prefill(q, k, v, window=win, interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert grew("flash_prefill") >= 1

    Tc = 17
    q = jnp.asarray(rng.standard_normal((3, Tc, NH, Dh)), f32)
    k = jnp.asarray(rng.standard_normal((3, Tc, KVH, Dh)), f32)
    v = jnp.asarray(rng.standard_normal((3, Tc, KVH, Dh)), f32)
    assert pallas_chunk.paged_chunk_supported(q, kp)
    past = jnp.asarray([0, 13, 24], jnp.int32)
    valid = jnp.asarray([Tc, Tc - 1, 1], jnp.int32)
    ops = dict(
        positions=past[:, None] + jnp.arange(Tc, dtype=jnp.int32)[None],
        valid_len=valid, past_k_pages=kp, past_v_pages=vp, layer=layer,
        page_table=table, past_len=past, window=jnp.asarray(0, jnp.int32),
    )
    want = chunk_attention(q, k, v, use_pallas=False, **ops)
    got = pallas_chunk.paged_chunk_attention(
        q, k, v, kp, vp, layer, table, past, valid, ops["window"],
        interpret=True,
    )
    live = np.arange(Tc)[None, :, None, None] < np.asarray(valid)[
        :, None, None, None]
    np.testing.assert_allclose(
        np.where(live, got, 0), np.where(live, want, 0), atol=2e-5, rtol=2e-5)
    assert grew("paged_chunk") >= 1


def test_a_call_that_goes_to_xla_is_counted_under_its_heads_with_its_gate():
    """``use_pallas`` at heads of 16: every gate refuses, and the report
    says so a head count, naming the gate."""
    rng = np.random.default_rng(3)
    f32 = jnp.float32
    kp = jnp.asarray(rng.standard_normal((1, 5, 4, 32)), f32)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    for NH in (4, 6):
        q = jnp.asarray(rng.standard_normal((1, 1, NH, 16)), f32)
        kv = jnp.asarray(rng.standard_normal((1, 1, 2, 16)), f32)
        chunk_attention(
            q, kv, kv, positions=jnp.asarray([[9]]),
            valid_len=jnp.ones(1, jnp.int32), past_k_pages=kp,
            past_v_pages=kp, layer=jnp.asarray(0), page_table=table,
            past_len=jnp.asarray([9]), use_pallas=True,
        )
        q = jnp.asarray(rng.standard_normal((1, 32, NH, 16)), f32)
        kv = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), f32)
        chunk_attention(
            q, kv, kv, positions=jnp.arange(32)[None],
            valid_len=jnp.asarray([32]), use_pallas=True,
        )
    seen = device_report(engine())["kernel_heads"]
    for NH in (4, 6):
        d, f = seen[f"paged_decode@{NH}"], seen[f"flash_prefill@{NH}"]
        assert d["reference"] >= 1 and "heads of 16" in d["gate"]
        assert f["reference"] >= 1 and "flash_prefill_supported" in f["gate"]
    # beside ``snapshot()`` and outside its keys
    assert set(lowering.snapshot()) == set(lowering.KERNELS)


# -- the routed layer, the write, the pools -----------------------------------


def test_a_routed_layer_a_tile_of_tokens_at_a_time_is_the_layer():
    p = transformer.init_params(MCFG, jax.random.PRNGKey(2), jnp.float32)
    lp = {k: v[3] for k, v in p["layers"]["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, MCFG.hidden_size))
    kw = dict(
        top_k=MCFG.moe_top_k, first_expert=0, return_counts=True,
        route=transformer._router_form(MCFG, lp),
    )
    args = (x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    whole, counts = moe.moe_mlp(*args, **kw)
    tiled, tiled_counts = moe.moe_mlp(*args, token_tile=16, **kw)
    np.testing.assert_allclose(tiled, whole, atol=1e-6, rtol=1e-5)
    assert np.array_equal(counts, tiled_counts)
    assert int(counts.sum()) == 48 * MCFG.moe_top_k
    # a tile that does not divide the dispatch: equal tiles under it
    # (48 tokens at most 20 at a time are three tiles of 16)
    again, again_counts = moe.moe_mlp(*args, token_tile=20, **kw)
    assert np.array_equal(again, tiled)
    assert np.array_equal(again_counts, counts)
    assert MCFG.moe_token_tile == 16 and CUT.moe_token_tile == 4096


def test_a_write_longer_than_one_call_takes_lands_in_runs(monkeypatch):
    from sutro_tpu.ops import pallas_kv

    runs = []
    real = pallas_kv._write_pools

    def counted(pools, news, *a, **k):
        runs.append(news[0].shape[2])
        return real(pools, news, *a, **k)

    monkeypatch.setattr(pallas_kv, "_write_pools", counted)
    # a run of 4 tokens of 64 in float32 is 1 KiB: the most a call
    # takes; 20 tokens land in five, and count as ONE write
    monkeypatch.setattr(pallas_kv, "RUN_BYTES", 4 * 64 * 4)
    pallas_kv.kv_write_pallas.clear_cache()
    cache = kvcache.alloc_cache(
        MODEL_CONFIGS["tiny-mellum2"], engine(), 16, dtype=jnp.float32)
    # (tiny-mellum2: 2 KV heads of 32 = 64 wide; its full pool alone)
    cache = dataclasses.replace(
        cache, wk_pages=None, wv_pages=None, window_page=None)
    L, KD = cache.k_pages.shape[0], cache.k_pages.shape[-1]
    assert KD == 64
    rng = np.random.default_rng(4)
    k = jnp.asarray(rng.standard_normal((L, 2, 20, KD)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, 2, 20, KD)), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 0], [7, 8, 9, 10, 11, 12, 0]])
    start = jnp.asarray([3, 0], jnp.int32)
    valid = jnp.asarray([20, 13], jnp.int32)
    want = kvcache.write_kv(cache, k, v, table, start, valid)
    before = lowering.snapshot()["kv_write"]["interpreted"]
    got_k, got_v = pallas_kv.kv_write_pallas(
        jnp.array(cache.k_pages), jnp.array(cache.v_pages), k, v, table,
        start, valid, interpret=True,
    )
    assert runs == [4, 4, 4, 4, 4]
    assert lowering.snapshot()["kv_write"]["interpreted"] == before + 1
    for a, b in ((got_k, want.k_pages), (got_v, want.v_pages)):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:])
    pallas_kv.kv_write_pallas.clear_cache()


def test_a_burst_of_window_bindings_is_told_in_one_shape(two_pools):
    """A job's rows released together change more page ids than one
    dispatch takes: several dispatches of the one shape, no new program
    (on the chip a second shape compiled inside the measured window in
    one run of six: PERF.md section 6, PR 61)."""
    r = two_pools
    was = np.asarray(r.cache.window_page).copy()
    told = ModelRunner._bind_window_jit
    r._tell_window(np.array([3], np.int32), np.array([was[3]], np.int32))
    programs = told._cache_size()
    ids = np.arange(1, 101, dtype=np.int32)
    r._tell_window(ids, (ids % 7).astype(np.int32))
    assert told._cache_size() == programs
    now = np.asarray(r.cache.window_page)
    assert np.array_equal(now[1:101], ids % 7)
    assert np.array_equal(now[101:], was[101:]) and now[0] == was[0]
    r._tell_window(ids, was[1:101])
    assert np.array_equal(np.asarray(r.cache.window_page), was)


def test_short_of_memory_the_full_pool_gives_way_before_the_window_pool():
    """A batch of 128 rows of 8,192 beside 6.4 GB of weights: the window
    pool keeps its span a row of the batch and the full pool takes what
    is left (divided in proportion the window pool held 54 rows')."""
    ecfg = EngineConfig(
        kv_page_size=64, max_pages_per_seq=128, decode_batch_size=128,
        max_model_len=8192, use_pallas=False,
    )
    limit, weights = int(15.75 * 2**30), 2 * 3_199_487_232
    stats = {"bytes_limit": limit, "bytes_in_use": weights}
    dev = types.SimpleNamespace(memory_stats=lambda: stats, device_kind="fake")
    layout = kvcache.cache_layout(CUT, ecfg, 2)
    fake = types.SimpleNamespace(
        mcfg=CUT, ecfg=ecfg, mesh=None, params={}, _margin_pages=0,
        window_span=layout.window_span, n_devices=1, _sized=layout,
    )
    fake._page_bytes_per_device = types.MethodType(
        ModelRunner._page_bytes_per_device, fake)
    real = jax.devices
    jax.devices = lambda *a: [dev]
    try:
        want_window = 1 + 128 * layout.window_span
        fit, win = ModelRunner._pages_that_fit(fake, 1 + 128 * 128, want_window)
        # and with room for everything, everything
        stats["bytes_in_use"] = 0
        stats["bytes_limit"] = 10 * limit
        assert ModelRunner._pages_that_fit(
            fake, 1 + 128 * 128, want_window) == (1 + 128 * 128, want_window)
    finally:
        jax.devices = real
    assert layout.window_span == 10 and win == want_window == 1281
    page, wpage = 3 * 2 * 64 * 1024 * 2, 6 * 2 * 64 * 1024 * 2
    left = limit - weights - int(limit * 0.2) - win * wpage
    assert fit == left // page and 6000 < fit < 7000
