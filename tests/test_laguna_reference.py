"""A model whose layer KINDS differ in their query heads (tiny-laguna:
full, window, window, window twice and a full layer; 4 query heads in a
full layer and 6 in a window layer over 2 KV heads, so 2 and 3 query
heads a KV head; a gate a head; a rotary part of half a head under YaRN
in the full layers, the whole head plainly in the window layers; window
8; a dense layer, then 16 experts top-3 of which 4 are held beside a
gated shared expert) against the plain float32 reference
(``perfbench/reference/laguna_moe.py``) on seeded random weights, on
every decode path the scheduler has, past the window, across page
boundaries (pages of 4) and across a release of window pages, with the
Pallas kernels in interpret mode and off.

What is compared is LOGITS where a path returns them (prefill, the
benchmark's own steps through the cache), else the token a greedy row
takes and the log-probability the program reports for it, against the
reference's at that position. Tolerance 2e-4 of the largest logit: both
sides compute in float32 and differ in summation order only; a window one
position off, a rotary part of the wrong width, a gate left out, a head
read from the wrong KV head or a page read after its release moves the
next positions by 1e-1 and more (the reference's own controls read 0.7).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import laguna_moe
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.test_kv_fetch_counters import _force_interpret

TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-laguna"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-laguna-cpu.json").read_text()
)
PS, MP, B = 4, 16, 4
W = MCFG.sliding_window


def engine(use_pallas: bool, **kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=B,
        max_model_len=PS * MP, use_pallas=use_pallas, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=12, seed=5,
        decode_multi_step=4,
    )
    base.update(kw)
    return EngineConfig(**base)


def _maker(kernels: bool, monkeypatch):
    """``make(two_pools)``: a runner with a window pool of its own (pages
    bound and released) or at the trivial setting (the identity map)."""
    if kernels:
        _force_interpret(monkeypatch)

    def make(two_pools: bool = True, **kw):
        ecfg = engine(kernels, **kw)
        r = ModelRunner(MCFG, ecfg, num_pages=1 + B * MP)
        if two_pools:
            r = ModelRunner(
                MCFG, ecfg, params=r.params, num_pages=1 + B * MP,
                window_pages=1 + B * r.window_span,
            )
            assert r.pools.window is not None
        return r

    return make


@pytest.fixture(params=["xla", "pallas"])
def make_runner(request, monkeypatch):
    """With the Pallas kernels in interpret mode and off: the
    benchmark's own steps, a chunk over a paged past and single steps
    across a release (each kernel of the path, at 2 and 3 query heads a
    KV head)."""
    return _maker(request.param == "pallas", monkeypatch)


@pytest.fixture
def make_xla_runner(monkeypatch):
    """The XLA forms alone, for the tests that hold the walk and the
    pools, which no kernel switch changes (the suite's room)."""
    return _maker(False, monkeypatch)


_REF = {}


def reference(params, ids):
    """Reference logits [T, V] at every position of ``ids``."""
    key = (id(params), tuple(int(i) for i in ids))
    if key not in _REF:
        _REF[key] = np.asarray(laguna_moe.logits_at(
            KEYS, params, list(ids), list(range(len(ids)))
        ))
    return _REF[key]


def close(got, want):
    err = np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()
    assert err < TOL, err


def logp_of(logits, tok):
    lg = np.asarray(logits, np.float64)
    return lg[tok] - (np.log(np.exp(lg - lg.max()).sum()) + lg.max())


def rows(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, n).astype(np.int32) for n in lengths]


def tables(n):
    t = np.zeros((B, MP), np.int32)
    for i in range(n):
        t[i] = np.arange(1 + i * MP, 1 + (i + 1) * MP)
    return t


def test_the_reference_reads_heads_rotary_width_and_gate_by_kind():
    dims = laguna_moe.dims_of(KEYS)
    assert dims["heads"] == (4, 6, 6, 6, 4, 6, 6, 6, 4)
    assert (dims["rot_full"], dims["rot_window"]) == (8, 16)
    plain, one = laguna_moe.inverse_frequencies(dims, "swa", 16)
    yarn, factor = laguna_moe.inverse_frequencies(dims, "attn", 8)
    assert one == 1.0 and abs(factor - (0.1 * np.log(8) + 1)) < 1e-12
    assert len(plain) == 8 and len(yarn) == 4 and plain[0] == yarn[0] == 1.0
    # the program's tables are the reference's, a kind at its own width
    from sutro_tpu.models.transformer import rope_inv_freq

    f, s = rope_inv_freq(MCFG.rope_theta, 4, MCFG, True)
    np.testing.assert_allclose(np.asarray(f), yarn, rtol=1e-6)
    assert abs(s - factor) < 1e-12
    f, s = rope_inv_freq(MCFG.local_rope_theta, 8, MCFG, False)
    np.testing.assert_allclose(np.asarray(f), plain, rtol=1e-6)
    # and the file's published form is read the same way: 64 of 128
    big = json.loads(
        (Path(correctness.__file__).parent
         / "configs/laguna-s-2.1-l9-ep8-v5e1.json").read_text()
    )
    d = laguna_moe.dims_of(big)
    assert d["heads"] == (48, 72, 72, 72, 48, 72, 72, 72, 48)
    assert (d["rot_full"], d["rot_window"], d["held"]) == (64, 128, 32)
    f, s = laguna_moe.inverse_frequencies(d, "attn", d["rot_full"])
    assert s == 1.4852030263919618 and f[0] == 1.0 and len(f) == 32
    assert abs(f[-1] * 128 * 500000 ** (31 / 32) - 1) < 1e-5
    # the past is turned inside the rotary part and passes beside it
    x = np.arange(2 * 3 * 16, dtype=np.float32).reshape(2, 3, 16)
    y = np.asarray(laguna_moe.rotary(
        jnp.asarray(x), jnp.asarray([0, 5]), yarn, factor))
    assert np.array_equal(y[:, :, 8:], x[:, :, 8:])
    assert not np.allclose(y[1, :, :8], x[1, :, :8])


@pytest.mark.parametrize("variant", laguna_moe.VARIANTS[1:])
def test_every_control_of_the_reference_fails_the_program(variant):
    r = ModelRunner(MCFG, engine(False), num_pages=1 + MP)
    (ids,) = rows(9, [30])
    got = r.prefill(ids, tables(1)[0])
    want = np.asarray(laguna_moe.logits_at(
        KEYS, r.params, list(ids), [29], variant=variant))[0]
    assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() > 0.05


def test_a_batched_prefill_of_rows_of_mixed_lengths_in_one_bucket(make_xla_runner):
    """Rows of 5 to 41 tokens in ONE dispatch, padded to the bucket of
    the longest: each row's logits are the reference's at its own end,
    the short rows past no window and the long one five windows long."""
    r = make_xla_runner()
    seqs = rows(7, [5, 41, 13, 27])
    t = tables(4)
    logits = np.asarray(r.prefill_batch(seqs, t))
    assert r.prefill_bucket(4, 41) == (4, 64)
    for i, s in enumerate(seqs):
        close(logits[i], reference(r.params, s)[-1])
    # and a step of all four reads what the batch wrote, each row's
    # pools at its own length
    past = np.array([len(s) for s in seqs], np.int32)
    last = np.array([int(np.argmax(logits[i])) for i in range(4)], np.int32)
    tok, logp = r.decode_step(
        last, past, t, jax.random.PRNGKey(0), np.zeros(B, np.float32),
        np.ones(B, np.float32),
    )
    for i in (0, 1):        # the shortest row and the longest
        at = reference(r.params, np.append(seqs[i], last[i]))[-1]
        assert tok[i] == int(np.argmax(at))
        assert abs(logp[i] - logp_of(at, tok[i])) < 1e-3


def test_prefill_past_the_window_and_the_benchmarks_steps(make_runner):
    """As ``perfbench/sut.py`` takes it: a small runner given its pool's
    size (the identity map), prefill, then single steps of given tokens
    through ``_trunk_decode`` and ``write_kv`` with ONE table."""
    r = make_runner(two_pools=False)
    assert r.pools.window is None
    assert np.array_equal(
        np.asarray(r.cache.window_page), np.arange(r.num_pages)
    )
    (ids,) = rows(1, [30])
    want = reference(r.params, ids)
    table = tables(1)[0]
    close(r.prefill(ids[:19], table), want[18])

    @jax.jit
    def step(params, cache, tok, past_len, page_table):
        logits, _, (k, v) = r._trunk_decode(
            params, cache, tok, past_len[:, None], past_len, page_table,
            kv_chunk=1,
        )
        cache = write_kv(
            cache, k, v, page_table, past_len, jnp.ones((1,), jnp.int32),
            use_pallas=r.use_pallas, kernel_mesh=r.kernel_mesh,
        )
        return logits[0, 0].astype(jnp.float32), cache

    cache = r.cache
    for j in range(19, 30):
        logits, cache = step(
            r.params, cache, jnp.asarray(ids[None, j : j + 1]),
            jnp.asarray([j], jnp.int32), jnp.asarray(table[None]),
        )
        close(logits, want[j])


def test_chunked_prefill_over_a_paged_window_past(make_runner):
    """A prompt over ``prefill_chunk``: each chunk attends over the
    window pages the chunks before it kept, binds what its own end still
    sees and gives back what slid out."""
    r = make_runner()
    (ids,) = rows(2, [41])
    table = tables(1)[0]
    close(r.prefill(ids, table), reference(r.params, ids)[-1])
    # four programs of a whole chunk each, as the padding is counted
    assert r.prefill_buckets([41]) == [(1, 12)] * 4
    assert r.prefill_buckets([5, 9]) == [r.prefill_bucket(2, 9)] == [(2, 16)]
    pool = r.pools.window
    # what is left bound is the window at the prompt's end, no more
    assert pool.released_total > 0
    assert pool.in_use <= (W + PS - 2) // PS + 1


def test_single_steps_across_pages_and_a_release(make_runner):
    r = make_runner()
    seqs = rows(3, [34, 29, 22])
    n0 = [11, 9, 6]
    want = [reference(r.params, s) for s in seqs]
    t = tables(3)
    for i, (s, n) in enumerate(zip(seqs, n0)):
        close(r.prefill(s[:n], t[i]), want[i][n - 1])
    pool, key = r.pools.window, jax.random.PRNGKey(0)
    steps = 16      # (the step's compile is the test's time, not its steps)
    for j in range(steps):
        past = np.array([n + j for n in n0] + [0], np.int32)
        last = np.array([s[p] for s, p in zip(seqs, past)] + [0], np.int32)
        tok, logp = r.decode_step(
            last, past, t, key, np.zeros(B, np.float32), np.ones(B, np.float32)
        )
        for i in range(3):
            at = want[i][past[i]]
            assert tok[i] == int(np.argmax(at))
            assert abs(logp[i] - logp_of(at, tok[i])) < 1e-3
        # the scheduler's part: committed lengths, then release
        r.pools.release_behind(t[:3], past[:3] + 1)
        assert pool.in_use <= 3 * ((W + PS - 2) // PS + 1)
    assert pool.released_total >= 3 * (steps // PS - 1)


def test_fused_windows_chained_across_a_release(make_xla_runner):
    """``decode_multi``: the sampled token feeds the next step on the
    device and the window's K/V is committed once, to each kind's pool;
    two windows, with the release between them that the scheduler makes
    from the committed lengths."""
    r = make_xla_runner()
    seqs = rows(4, [13, 10])
    t = tables(2)
    firsts = []
    for i, s in enumerate(seqs):
        firsts.append(int(np.argmax(r.prefill(s, t[i]))))
    past = np.array([len(s) for s in seqs] + [0, 0], np.int32)
    last = np.array(firsts + [0, 0], np.int32)
    out = [list(s) + [f] for s, f in zip(seqs, firsts)]
    got_lp = [[], []]
    steps = 4
    for _ in range(3):
        toks, logps = r.decode_multi(
            last, past, t, jax.random.PRNGKey(1), np.zeros(B, np.float32),
            np.ones(B, np.float32), steps,
        )
        for i in range(2):
            out[i] += [int(x) for x in toks[:, i]]
            got_lp[i] += [float(x) for x in logps[:, i]]
        past = past + np.array([steps, steps, 0, 0], np.int32)
        last = np.array([o[-1] for o in out] + [0, 0], np.int32)
        r.pools.release_behind(t[:2], past[:2])
    assert r.pools.window.released_total > 0
    for i in range(2):
        want = reference(r.params, np.array(out[i][:-1], np.int32))
        n = len(seqs[i])
        for j in range(3 * steps):
            at = want[n + j]
            assert out[i][n + 1 + j] == int(np.argmax(at))
            assert abs(got_lp[i][j] - logp_of(at, out[i][n + 1 + j])) < 1e-3


def test_a_verify_chunk_over_the_paged_past(make_xla_runner):
    """``verify_candidates``: T > 1 over a paged past gathers a window
    layer's live pages only; every input's K/V is written."""
    r = make_xla_runner()
    (ids,) = rows(5, [30])
    want = reference(r.params, ids)
    t = tables(1)
    r.prefill(ids[:17], t[0])
    r.pools.release_behind(t[:1], [17])
    K = 6
    drafts = np.zeros((B, K), np.int32)
    drafts[0] = ids[18 : 18 + K]
    ct, cl, pt, pl = r.verify_candidates(
        np.array([ids[17], 0, 0, 0], np.int32), drafts,
        np.array([K, 0, 0, 0], np.int32), np.zeros((B, K + 1, 1), np.int32),
        np.zeros((B, K + 1), np.int32), np.array([17, 0, 0, 0], np.int32), t,
    )
    for j in range(K + 1):
        at = want[17 + j]
        assert pt[0, j] == int(np.argmax(at))
        assert abs(pl[0, j] - logp_of(at, pt[0, j])) < 1e-3
    # and a step after the chunk reads what the chunk wrote
    past = np.array([17 + K + 1, 0, 0, 0], np.int32)
    r.pools.release_behind(t[:1], past[:1])
    tok, logp = r.decode_step(
        np.array([ids[past[0]], 0, 0, 0], np.int32), past, t,
        jax.random.PRNGKey(0), np.zeros(B, np.float32), np.ones(B, np.float32),
    )
    assert tok[0] == int(np.argmax(want[past[0]]))
    assert abs(logp[0] - logp_of(want[past[0]], tok[0])) < 1e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """What the four shares of four experts compute, with the GATED
    shared expert counted ONCE, is what the uncut reference gives for
    the whole layer (the published eight shares of thirty-two, at the
    preset's size)."""
    import dataclasses

    from sutro_tpu.models import transformer
    from sutro_tpu.ops import moe

    cfg = dataclasses.replace(MCFG, name="tiny-laguna: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    keys = dict(KEYS, share={})
    d = laguna_moe.dims_of(keys)
    assert (d["held"], d["experts"], d["scale"]) == (16, 16, 2.5)
    moe_l = params["layers"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 5, cfg.hidden_size))
    index, held = 2, MCFG.experts_held
    flat = x.reshape(15, -1)
    with jax.default_matmul_precision("highest"):
        whole, _ = laguna_moe.routed_ffn(d, moe_l, index, flat, shared=True)
        routed_only, _ = laguna_moe.routed_ffn(
            d, moe_l, index, flat, shared=False)
    only_shared = np.asarray(whole) - np.asarray(routed_only)
    assert np.abs(only_shared).max() > 1e-2        # and its gate bites
    lp = {k: v[index] for k, v in moe_l.items()}
    total = np.zeros((3, 5, cfg.hidden_size), np.float32)
    counted = 0
    for first in range(0, cfg.moe_experts, held):
        share_cfg = dataclasses.replace(
            cfg, moe_experts_held=held, moe_first_expert=first
        )
        y, counts = moe.moe_mlp(
            x, lp["router"], lp["we_gate"][first : first + held],
            lp["we_up"][first : first + held],
            lp["we_down"][first : first + held],
            top_k=cfg.moe_top_k, activation=cfg.activation,
            first_expert=first, route=transformer._router_form(share_cfg, lp),
            return_counts=True,
        )
        total += np.asarray(y)
        counted += int(counts[first : first + held].sum())
    assert counted == 15 * cfg.moe_top_k     # each assignment on ONE share
    # the program's shared expert under its gate, once
    share_cfg = dataclasses.replace(cfg, moe_experts_held=held)
    one_share = {k: (v[:held] if k.startswith("we_") else v)
                 for k, v in lp.items()}
    with_shared = transformer._mlp(share_cfg, one_share, x)
    y0, _ = moe.moe_mlp(
        x, lp["router"], lp["we_gate"][:held], lp["we_up"][:held],
        lp["we_down"][:held], top_k=cfg.moe_top_k, first_expert=0,
        route=transformer._router_form(share_cfg, lp), return_counts=True,
    )
    program_shared = np.asarray(with_shared) - np.asarray(y0)
    scale = float(np.abs(np.asarray(whole)).max())
    assert np.abs(
        program_shared.reshape(15, -1) - only_shared).max() < 1e-5 * scale
    total += program_shared
    assert np.abs(total.reshape(15, -1) - np.asarray(whole)).max() < 1e-5 * scale
