"""A model whose attention layers are of two kinds (tiny-mellum2: window,
window, window, full; window 8, YaRN on the full layer, 8 experts top-2)
against the plain float32 reference
(``perfbench/reference/mellum2_moe.py``) on seeded random weights, on
every decode path the scheduler has, past the window, across page
boundaries (pages of 4) and across a release of window pages, with the
Pallas kernels in interpret mode and off.

What is compared is LOGITS where a path returns them (prefill, the
benchmark's own steps through the cache), else the token a greedy row
takes and the log-probability the program reports for it, against the
reference's at that position. Tolerance 2e-4 of the largest logit: both
sides compute in float32 and differ in summation order only; a window one
position off, a rotary embedding of the wrong kind or a page read after
its release moves the next positions by 1e-1 and more.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import mellum2_moe
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.test_kv_fetch_counters import _force_interpret

TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-mellum2"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-mellum2-cpu.json").read_text()
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


@pytest.fixture(params=["xla", "pallas"])
def make_runner(request, monkeypatch):
    """``make(two_pools)``: a runner with a window pool of its own (pages
    bound and released) or at the trivial setting (the identity map)."""
    kernels = request.param == "pallas"
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


_REF = {}


def reference(params, ids):
    """Reference logits [T, V] at every position of ``ids``."""
    key = (id(params), tuple(int(i) for i in ids))
    if key not in _REF:
        _REF[key] = np.asarray(mellum2_moe.logits_at(
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


def test_the_reference_reads_the_rotary_embedding_by_kind():
    dims = mellum2_moe.dims_of(KEYS)
    plain, one = mellum2_moe.inverse_frequencies(dims, "swa")
    yarn, factor = mellum2_moe.inverse_frequencies(dims, "attn")
    assert one == 1.0 and factor == 1.2772588722239782
    assert plain[0] == 1.0 and np.all(yarn <= plain)
    # the slowest pair is interpolated by the whole factor
    assert abs(yarn[-1] * 16 / plain[-1] - 1) < 1e-6
    # and the file's published form is read the same way
    big = json.loads(
        (Path(correctness.__file__).parent
         / "configs/mellum2-12b-a2.5b-l8-v5e1.json").read_text()
    )
    d = mellum2_moe.dims_of(big)
    f, s = mellum2_moe.inverse_frequencies(d, "attn")
    assert s == 1.2772588722239782 and f[0] == 1.0
    assert abs(f[-1] * 16 * 500000 ** (63 / 64) - 1) < 1e-5


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
    for j in range(16):
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
    assert pool.released_total >= 3 * (16 // PS - 1)


def test_fused_windows_chained_across_a_release(make_runner):
    """``decode_multi``: the sampled token feeds the next step on the
    device and the window's K/V is committed once, to each kind's pool;
    two windows, with the release between them that the scheduler makes
    from the committed lengths."""
    r = make_runner()
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


def test_a_verify_chunk_over_the_paged_past(make_runner):
    """``verify_candidates``: T > 1 over a paged past gathers a window
    layer's live pages only; every input's K/V is written."""
    r = make_runner()
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
