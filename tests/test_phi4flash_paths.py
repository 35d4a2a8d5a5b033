"""Which paths a decoder-hybrid-decoder takes and which it refuses, by
name: the presets and the ONE description of its state and its pools
(the full pool is ONE layer, and eight layers' reads land on it); rows
through the scheduler that hold a slot, window pages and full pages at
once, bound and released together by ``RowPools``, with the spans and
counters that say what a step read; the trace-time counts of the forms
its layers took; and what is not built, refused."""

import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import sambay_diff
from sutro_tpu import telemetry
from sutro_tpu.engine import kvcache
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.prefixstore import PrefixStore
from sutro_tpu.engine.runner import ModelRunner, device_report
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS, sambay_layers
from sutro_tpu.ops import lowering

MCFG = MODEL_CONFIGS["tiny-phi4flash"]
WHOLE = MODEL_CONFIGS["phi-4-mini-flash-reasoning"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-phi4flash-cpu.json").read_text()
)
PS, MP, B = 4, 32, 4
TOK = ByteTokenizer(vocab_size=MCFG.vocab_size)


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=B,
        max_model_len=PS * MP, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=PS * MP, seed=9,
        decode_multi_step=4, prefill_batch_size=1,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def runner():
    first = ModelRunner(MCFG, engine(), num_pages=1 + B * MP)
    r = ModelRunner(
        MCFG, engine(), params=first.params, num_pages=1 + B * MP,
        window_pages=1 + B * first.window_span,
    )
    assert r.pools.window is not None and r.pools.slots is not None
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


# -- the presets and the one description ------------------------------------


def test_the_presets_follow_the_published_rule_and_one_state_description():
    assert WHOLE.mixers == sambay_layers(32) and MCFG.mixers == sambay_layers(8)
    assert [WHOLE.mixers.count(k) for k in
            ("mamba1", "swa", "attention", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert (WHOLE.memory_layer, WHOLE.kv_source_layer) == (16, 17)
    assert (WHOLE.num_heads, WHOLE.num_kv_heads, WHOLE.head_dim) == (40, 20, 64)
    assert WHOLE.kernel_head_dim == 128 and MCFG.kernel_head_dim == 16
    assert (WHOLE.mamba1_inner, WHOLE.mamba1_state, WHOLE.mamba1_dt_rank) == (
        5120, 16, 160)
    # THE places the readers read: one kind of state a model, its slot's
    # shape, and the pools' depths
    for cfg, inner, rows_ in ((WHOLE, 5120, 16), (MCFG, 128, 4)):
        assert cfg.state_kind == "mamba1"
        assert (cfg.state_inner, cfg.state_rows) == (inner, rows_)
        assert (cfg.state_conv_dim, cfg.state_conv_len) == (inner, 3)
    assert (WHOLE.num_state_layers, WHOLE.num_pool_layers,
            WHOLE.num_window_layers, WHOLE.num_kv_layers) == (9, 1, 8, 9)
    assert WHOLE.kv_readers("attention") == 8 == WHOLE.kv_readers("swa")
    assert MCFG.kv_readers("attention") == 2 == MCFG.kv_readers("swa")
    assert WHOLE.page_width == 1280 and WHOLE.pool_row_widths == (1280, 1280)
    # the two periods scan; the layers that hand a value down stand alone
    assert transformer.layer_groups(WHOLE) == [
        (0, 2, 8), (16, 1, 1), (17, 1, 1), (18, 2, 7)]
    # the families that came before read the same description as before
    g, s = MODEL_CONFIGS["tiny-granite"], MODEL_CONFIGS["tiny-solar-kda"]
    assert (g.state_kind, g.state_rows, g.state_inner, g.state_conv_len) == (
        "mamba", 16, 256, 3)
    assert (s.state_kind, s.state_rows, s.state_inner, s.state_conv_dim) == (
        "kda", 16, 64, 192)
    assert g.kv_readers("attention") == g.num_attn_layers


def test_the_full_pool_is_one_layer_and_a_row_holds_all_three_kinds(runner):
    c, lay = runner.cache, runner.layout
    assert c.k_pages.shape == (1, 1 + B * MP, PS, 32)
    assert c.wk_pages.shape[0] == 2 and c.wk_pages.shape[1] == lay.window_pages
    assert c.ssm.shape == (3, 1 + B, 4, 128)
    assert c.ssm_conv.shape == (1 + B, 3 * 3 * 128)
    assert lay.state_kind == "mamba1" and lay.has_state and lay.binds_window
    # a token keeps K and V of ONE layer over its context
    assert lay.page_bytes == PS * 32 * 2 * 4
    assert lay.slot_bytes == 3 * (4 + 3) * 128 * 4
    info = runner.device_info()
    assert (info["pool_layers"], info["window_layers"], info["state_layers"],
            info["state_kind"]) == (1, 2, 3, "mamba1")
    # slots and window pages refuse what each refused alone, the slot
    # first; nothing new is asked of the prefix store or the tiers
    assert lay.refuses("share") == "prefix_without_state_snapshot"
    assert lay.refuses("tiers") == "hibernate_without_slot_state"
    assert "window pages" in lay.refuses("read_pages")


def test_a_cross_layers_reads_land_on_the_full_layers_pool_layer(runner):
    """Layer 7 has no pool layer of its own: with the pages of pool layer
    0 (layer 5's) zeroed after a prefill, the next step's logits move
    exactly as they do for the reference whose cross layer sees nothing
    of the past; zeroing the window pool leaves the cross layer alone."""
    r = runner
    r.pools.reset()
    ids = np.random.default_rng(3).integers(3, 250, 21).astype(np.int32)
    t = np.zeros((B, MP), np.int32)
    t[0] = np.arange(1, 1 + MP)
    r.prefill(ids[:20], t[0])
    past = np.array([20, 0, 0, 0], np.int32)
    last = np.array([ids[20], 0, 0, 0], np.int32)

    def step(cache):
        logits, _, _ = r._trunk_decode(
            r.params, cache, jnp.asarray(last[:, None]),
            jnp.asarray(past[:, None]), jnp.asarray(past), jnp.asarray(t),
        )
        return np.asarray(logits[0, 0])

    want = np.asarray(sambay_diff.logits_at(KEYS, r.params, list(ids), [20]))[0]
    whole = step(r.cache)
    assert np.abs(whole - want).max() / np.abs(want).max() < 2e-4
    no_full = step(dataclasses.replace(
        r.cache, k_pages=jnp.zeros_like(r.cache.k_pages),
        v_pages=jnp.zeros_like(r.cache.v_pages)))
    assert np.abs(no_full - whole).max() / np.abs(whole).max() > 1e-2
    # two readers, ONE pool layer: nothing else could have held it
    assert r.cache.k_pages.shape[0] == 1 and MCFG.kv_readers("attention") == 2


def test_rows_hold_and_release_a_slot_window_pages_and_full_pages_together(runner):
    """Six rows through a batch of four: admission binds a slot and a
    window budget with the row's pages and gives all three back with
    them; the spans say how many layers read each pool and keep a state,
    the counter whose K/V each read was."""
    prompts = ["the first prompt, a little longer than a window of eight",
               "a second", "and a third one of middling length", "a fourth",
               "a fifth that waits for a slot and a budget", "the sixth"]
    own0 = counter("sutro_kv_read_tokens_total", "own")
    shared0 = counter("sutro_kv_read_tokens_total", "shared")
    full_own0 = counter("sutro_kv_read_tokens_total", "full,own")
    started = time.monotonic() - telemetry.RECORDER.epoch_mono
    b = ContinuousBatcher(runner, stop_ids=[])
    got = run(b, reqs(prompts, new=14))
    for i, p in enumerate(prompts):
        ids = TOK.encode(p) + got[i]
        want = np.asarray(sambay_diff.logits_at(
            KEYS, runner.params, ids[:-1],
            list(range(len(ids) - 15, len(ids) - 1)),
        ))
        assert got[i] == [int(t) for t in want.argmax(-1)]
    pools = runner.pools
    assert pools.slots.in_use == 0 and pools.slots.total == B
    assert pools.window.in_use == 0 and pools.window.released_total > 0
    assert pools.window.budget_free == pools.window.total
    windows = [
        s["attrs"] for s in telemetry.RECORDER.snapshot()
        if s["name"] == "decode_window" and s["t0_s"] >= started
    ]
    assert windows and all(
        (a["kv_readers_full"], a["kv_readers_window"], a["state_layers"])
        == (2, 2, 3)
        and a["kv_tokens_window"] <= min(a["kv_tokens_full"], 8)
        and a["state_rows"] >= 1
        for a in windows
    )
    own = counter("sutro_kv_read_tokens_total", "own") - own0
    shared = counter("sutro_kv_read_tokens_total", "shared") - shared0
    full_own = counter("sutro_kv_read_tokens_total", "full,own") - full_own0
    # the cross layer read what the full layer read, token for token
    assert shared == full_own > 0 and own > shared
    forms = lowering.mamba1_counts()
    assert min(forms["chunked"], forms["window"]) > 0
    assert device_report(runner.ecfg)["mamba1"] == forms


def test_rows_that_share_a_prefix_prefill_it_again_and_say_so(runner):
    shell = "one shared shell of twenty-odd bytes, then: "
    prompts = [shell + t for t in ("alpha", "beta beta", "gamma")]
    name = "sutro_state_fallback_prefill_tokens_total"
    before = counter(name, "prefix_without_state_snapshot")
    b = ContinuousBatcher(runner, stop_ids=[], prefix_store=PrefixStore(PS))
    assert b._prefix_store is None            # no page holds the state
    run(b, reqs(prompts, new=4))
    shared = (len(TOK.encode(shell)) // PS) * PS
    assert counter(name, "prefix_without_state_snapshot") - before == 2 * shared


def test_the_decode_fetch_count_reads_the_pair_as_the_kernels_head(monkeypatch):
    """``_count_kv_pages`` asks the paged kernel's gate about the head the
    kernel sees (a pair of 128 at the published size), and counts every
    READER of a pool: 8 + 8 layer-fetches a step, not 1 + 8."""
    from sutro_tpu.ops import pallas_paged

    seen = []
    monkeypatch.setattr(
        pallas_paged, "paged_decode_supported",
        lambda q, pages, *a: seen.append(q.shape[-1]) or True,
    )
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg = WHOLE, engine(kv_page_size=64)
    r.use_pallas, r.kernel_mesh, r._kv_pages = True, None, None
    r.cache = dataclasses.make_dataclass("C", ["k_pages"])(
        jax.ShapeDtypeStruct((1, 9, 64, 1280), jnp.bfloat16))
    r._count_kv_pages(np.array([640]), np.zeros((1, 32), np.int32), 1, None)
    assert seen == [128]
    fetched, needed = r.take_kv_pages()
    # a full reader fetches 10 pages, a window reader the 8 from the
    # page of position 640 - 511 on
    assert fetched == 8 * 10 + 8 * 8 and needed == 8 * 10 + 8 * 511 / 64


# -- what is not built --------------------------------------------------------


def test_what_is_not_built_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="no int8 scale pools"):
        ModelRunner(MCFG, engine(kv_quantize="int8"), num_pages=9)
    with pytest.raises(NotImplementedError, match="several kinds"):
        ModelRunner(MCFG, engine(quantize="int8"), num_pages=9)
    if jax.device_count() >= 2:
        with pytest.raises(NotImplementedError, match="under a mesh"):
            ModelRunner(MCFG, engine(tp=2), num_pages=9)
    check = transformer._check_mixed
    check(MCFG)
    bad = dataclasses.replace
    with pytest.raises(NotImplementedError, match="state layers of two kinds"):
        check(bad(MCFG, layer_types=("mamba",) + MCFG.layer_types[1:],
                  mamba_heads=4, mamba_head_dim=8, mamba_state=4, mamba_conv=4))
    with pytest.raises(ValueError, match="memory_layer=3"):
        check(bad(MCFG, memory_layer=3))
    with pytest.raises(ValueError, match="kv_source_layer=7"):
        check(bad(MCFG, kv_source_layer=7))
    with pytest.raises(ValueError, match="mamba1_conv >= 2"):
        check(bad(MCFG, mamba1_dt_rank=0))
    with pytest.raises(ValueError, match="block_norm 'batchnorm'"):
        check(bad(MCFG, block_norm="batchnorm"))
    with pytest.raises(NotImplementedError, match="differential attention"):
        check(bad(MCFG, position_embedding="rope"))
    with pytest.raises(NotImplementedError, match="differential attention"):
        check(bad(MCFG, num_kv_heads=3))
    # biases: built on attention layers, still refused by name elsewhere
    with pytest.raises(NotImplementedError, match="moe_bias"):
        check(bad(MCFG, moe_bias=True))
    with pytest.raises(NotImplementedError, match="latent"):
        check(bad(MODEL_CONFIGS["tiny-joyai"], attn_bias=True))
    # the one scan of a homogeneous model builds neither
    for field in (dict(attn_differential=True), dict(block_norm="layernorm")):
        with pytest.raises(NotImplementedError, match="layer_types"):
            transformer._init_params(
                bad(MODEL_CONFIGS["tiny-dense"], name="a dense model",
                    **field),
                jax.random.PRNGKey(0), jnp.float32,
            )
    # a checkpoint of it is refused, not loaded as another family's
    from sutro_tpu.engine import weights

    with pytest.raises(NotImplementedError):
        weights._load_mixed(MCFG, lambda *a, **k: None, jnp.float32)


def test_one_block_norm_in_one_place():
    """``block_norm`` reads the field: LayerNorm with its bias where the
    model says so (the indexer's ``layer_norm``, not a second copy),
    RMSNorm everywhere else."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64))
    lp = {"attn_norm": jnp.full((64,), 1.5), "attn_norm_b": jnp.full((64,), 0.25)}
    got = transformer.block_norm(MCFG, x, lp, "attn_norm")
    want = (x - x.mean(-1, keepdims=True)) / jnp.sqrt(
        x.var(-1, keepdims=True) + MCFG.norm_eps) * 1.5 + 0.25
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    dense = MODEL_CONFIGS["tiny-dense"]
    np.testing.assert_allclose(
        np.asarray(transformer.block_norm(dense, x, lp, "attn_norm")),
        np.asarray(transformer.rms_norm(x, lp["attn_norm"], dense.norm_eps, False)),
    )
    params = jax.eval_shape(
        lambda k: transformer.init_params(MCFG, k, jnp.float32),
        jax.random.PRNGKey(0))
    assert "final_norm_b" in params
    assert all(
        ("mlp_norm_b" if kind == "dense" else "attn_norm_b") in stack
        for kind, stack in params["layers"].items()
    )


def test_a_long_write_lands_in_runs_of_a_power_of_two_of_tokens(monkeypatch):
    """A chunk longer than one call of the K/V write kernel takes lands
    in runs; a run is a POWER OF TWO of tokens (the kernel rolls a run's
    rows by a dynamic shift: over the 768 rows that 2 MiB hold of this
    model's 1,280-wide rows the chip put a prompt's later tokens into the
    wrong page rows, PERF.md section 6, PR 64), and rows of 512 and 1,024
    values keep the runs they had."""
    from sutro_tpu.ops import pallas_kv

    seen = []

    def fake(pools, news, table, start, valid, interpret):
        seen.append((news[0].shape[2], news[0].shape[3]))
        return pools

    monkeypatch.setattr(pallas_kv, "_write_pools", fake)
    for KD, T, want in ((1280, 2048, [512] * 4), (1280, 1024, [512, 512]),
                        (1024, 8192, [1024] * 8), (512, 2048, [2048]),
                        (1280, 8, [8])):
        seen.clear()
        pool = jnp.zeros((1, 3, 64, KD), jnp.bfloat16)
        new = jnp.zeros((1, 1, T, KD), jnp.bfloat16)
        pallas_kv.kv_write_pallas.__wrapped__(
            pool, pool, new, new, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), T, jnp.int32),
        )
        assert [t for t, _ in seen] == want, (KD, T, seen)
    # and the kernel itself, interpreted, writes a chunk of two runs
    # where the scatter writes it
    monkeypatch.undo()
    KD, T, PS = 1280, 1024, 64
    pool = jnp.zeros((1, 20, PS, KD), jnp.float32)
    new = jax.random.normal(jax.random.PRNGKey(2), (1, 1, T, KD))
    table = jnp.arange(1, 19, dtype=jnp.int32)[None]
    k2, _ = pallas_kv.kv_write_pallas(
        pool, jnp.zeros_like(pool), new, new, table,
        jnp.zeros((1,), jnp.int32), jnp.array([700], jnp.int32),
        interpret=True,
    )
    got = np.asarray(k2)[0, 1:12].reshape(-1, KD)[:700]
    np.testing.assert_array_equal(got, np.asarray(new)[0, 0, :700])
    assert not np.asarray(k2)[0, 12:].any()
