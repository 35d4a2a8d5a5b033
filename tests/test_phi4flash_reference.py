"""A decoder-hybrid-decoder with differential attention (tiny-phi4flash:
Mamba-1 in layers 0, 2, 4, differential attention over a window of 8 in
layers 1, 3 and over everything in layer 5, a gated memory unit fed by
layer 4's scan output in layer 6, a cross layer over layer 5's K/V in
layer 7; 8 query and 4 KV heads of 8, so 4 differential heads over 2 KV
pairs; LayerNorm blocks, projection biases) against the plain float32
reference (``perfbench/reference/sambay_diff.py``) on seeded random
weights, on every decode path the scheduler has, past the window, across
page boundaries (pages of 4), across a release of window pages and
across commits of the state slots, with the Pallas kernels in interpret
mode and off.

What is compared is LOGITS where a path returns them (prefill, the
benchmark's own steps through the cache), else the token a greedy row
takes and the log-probability the program reports for it, against the
reference's at that position. Tolerance 2e-4 of the largest logit: both
sides compute in float32 and differ in summation order only (the
program's scan runs in chunks and its heads in pairs); a window one
position off, a cross layer reading the wrong pool layer, a memory taken
after the gate, a lambda left out or a state committed a token late
moves the next positions by 1e-1 and more (the reference's own controls
read 0.1-0.5).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import sambay_diff
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.test_kv_fetch_counters import _force_interpret

TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-phi4flash"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-phi4flash-cpu.json").read_text()
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
            assert r.pools.window is not None and r.pools.slots is not None
        return r

    return make


@pytest.fixture(params=["xla", "pallas"])
def make_runner(request, monkeypatch):
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
        _REF[key] = np.asarray(sambay_diff.logits_at(
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


def test_the_reference_lays_the_layers_out_by_the_published_rule():
    d = sambay_diff.dims_of(KEYS)
    assert d["kinds"] == (
        "mamba1", "swa", "mamba1", "swa", "mamba1", "attn", "gmu", "cross")
    assert tuple(
        {"attn": "attention"}.get(k, k) for k in d["kinds"]) == MCFG.mixers
    assert (MCFG.memory_layer, MCFG.kv_source_layer) == (4, 5) == (
        d["half"], d["half"] + 1)
    big = json.loads(
        (Path(correctness.__file__).parent
         / "configs/phi-4-mini-flash-reasoning-v5e1.json").read_text()
    )
    d = sambay_diff.dims_of(big)
    kinds = d["kinds"]
    assert [kinds.count(k) for k in ("mamba1", "swa", "attn", "gmu", "cross")] == [
        9, 8, 1, 7, 7]
    assert kinds[16:20] == ("mamba1", "attn", "gmu", "cross")
    assert (d["inner"], d["state"], d["conv"], d["dt_rank"], d["head_dim"]) == (
        5120, 16, 4, 160, 64)
    from sutro_tpu.models.transformer import lambda_init

    for depth in (1, 17, 31):
        assert abs(float(lambda_init(depth))
                   - (0.8 - 0.6 * np.exp(-0.3 * depth))) < 1e-6


@pytest.mark.parametrize("variant", sambay_diff.VARIANTS)
def test_every_control_of_the_reference_fails_the_program(variant):
    r = ModelRunner(MCFG, engine(False), num_pages=1 + MP)
    (ids,) = rows(9, [30])
    got = r.prefill(ids, tables(1)[0])
    want = np.asarray(sambay_diff.logits_at(
        KEYS, r.params, list(ids), [29], variant=variant))[0]
    assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() > 0.05


def test_the_pair_form_is_the_two_softmaxes_head_by_head():
    """The 128-wide call of the kernels' callers (here 16-wide: queries
    ``[q1 | 0]`` and ``[0 | q2]`` over KV pairs ``[k1 | k2]``, ``[v1 |
    v2]``, a group of 4, the scale folded into q) gives, query head by
    query head, ``softmax(q_c k_c^T / sqrt(Dh)) [v1 | v2]``: the two
    64-wide softmaxes of the reference."""
    from sutro_tpu.ops.attention import chunk_attention

    T, NH, KVH, Dh = 13, 8, 4, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (1, T, NH, Dh))
    k = jax.random.normal(kk, (1, T, KVH, Dh))
    v = jax.random.normal(kv, (1, T, KVH, Dh))
    even = (jnp.arange(NH) % 2 == 0)[:, None]
    q2 = jnp.concatenate([jnp.where(even, q, 0), jnp.where(even, 0, q)], -1)
    pos = jnp.arange(T)[None]
    got = chunk_attention(
        q2 * (Dh ** -0.5 * (2 * Dh) ** 0.5),
        k.reshape(1, T, KVH // 2, 2 * Dh), v.reshape(1, T, KVH // 2, 2 * Dh),
        positions=pos, valid_len=jnp.array([T]),
    )[0]                                                   # [T, NH, 2 Dh]
    seen = np.tril(np.ones((T, T), bool))
    for n in range(NH):
        pair, c = n // 4, n % 2
        s = np.asarray(q[0, :, n] @ k[0, :, 2 * pair + c].T) * Dh ** -0.5
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = p @ np.asarray(v[0].reshape(T, KVH // 2, 2 * Dh)[:, pair])
        np.testing.assert_allclose(np.asarray(got[:, n]), want, atol=2e-5)


def test_a_batched_prefill_of_rows_of_mixed_lengths_in_one_bucket(make_xla_runner):
    """Rows of 5 to 41 tokens in ONE dispatch, padded to the bucket of
    the longest: each row's logits are the reference's at its own end
    (a padded token's ``dt`` is 0: it neither decays nor feeds a state),
    the short rows past no window and the long one five windows long."""
    r = make_xla_runner()
    seqs = rows(7, [5, 41, 13, 27])
    t = tables(4)
    logits = np.asarray(r.prefill_batch(seqs, t))
    for i, s in enumerate(seqs):
        close(logits[i], reference(r.params, s)[-1])
    # and a step of all four reads what the batch wrote, each row's
    # slot and pools at its own length
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
    through ``_trunk_decode`` and ``write_kv`` with ONE table: the state,
    the window K/V and the one full layer's K/V all ride that pair."""
    r = make_runner(two_pools=False)
    assert r.pools.window is None
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


def test_chunked_prefill_where_a_cross_layer_reads_a_past_and_a_chunk(make_runner):
    """A prompt over ``prefill_chunk``: each chunk's Mamba-1 layers start
    from the slot the chunk before committed, its window layers attend
    over the window pages kept, and layer 7 attends over LAYER 5's pages
    (pool layer 0) AND layer 5's chunk K/V, handed down the stack."""
    r = make_runner()
    (ids,) = rows(2, [41])
    table = tables(1)[0]
    close(r.prefill(ids, table), reference(r.params, ids)[-1])
    assert r.prefill_buckets([41]) == [(1, 12)] * 4
    pool = r.pools.window
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
    assert r.pools.slots.in_use == 3


def test_fused_windows_chained_across_state_commits(make_xla_runner):
    """``decode_multi``: the sampled token feeds the next step on the
    device, the rows' Mamba-1 state rides the scan in float32 and the
    window's tokens commit it ONCE, beside the K/V of each kind's pool;
    three windows, each starting from the slot the one before wrote,
    with the release between them that the scheduler makes."""
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


def test_a_speculative_window_commits_any_accepted_length(make_xla_runner):
    """``decode_window`` / ``commit_window``: the window's tokens commit
    the state for the accepted PREFIX (3 of 4 steps), from the pool and
    the tokens, whatever state the scan carried to the window's end."""
    r = make_xla_runner()
    (ids,) = rows(6, [40])
    want = reference(r.params, ids)
    t = tables(1)
    r.prefill(ids[:15], t[0])
    past = np.array([15, 0, 0, 0], np.int32)
    toks, _, handle = r.decode_window(
        np.array([ids[15], 0, 0, 0], np.int32), past, t,
        jax.random.PRNGKey(0), np.zeros(B, np.float32),
        np.ones(B, np.float32), 4,
    )
    r.commit_window(handle, np.array([3, 0, 0, 0], np.int32))
    # the row goes on from 15 + 3 given tokens (the window's own, greedy)
    seq = np.array(list(ids[:16]) + [int(x) for x in toks[:3, 0]], np.int32)
    full = reference(r.params, seq)
    assert [int(np.argmax(full[15 + j])) for j in range(3)] == [
        int(x) for x in toks[:3, 0]]
    past = np.array([18, 0, 0, 0], np.int32)
    tok, logp = r.decode_step(
        np.array([seq[18], 0, 0, 0], np.int32), past, t,
        jax.random.PRNGKey(0), np.zeros(B, np.float32), np.ones(B, np.float32),
    )
    assert tok[0] == int(np.argmax(full[18]))
    assert abs(logp[0] - logp_of(full[18], tok[0])) < 1e-3
    del want


def test_a_verify_chunk_over_the_paged_past(make_xla_runner):
    """``verify_candidates``: T > 1 over a paged past steps the state a
    token at a time from the slot without writing it; the accepted
    length commits it (``commit_verified``)."""
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
    # four of the seven inputs are accepted: the state after them
    r.commit_verified(np.array([4, 0, 0, 0], np.int32))
    past = np.array([17 + 4, 0, 0, 0], np.int32)
    r.pools.release_behind(t[:1], past[:1])
    tok, logp = r.decode_step(
        np.array([ids[past[0]], 0, 0, 0], np.int32), past, t,
        jax.random.PRNGKey(0), np.zeros(B, np.float32), np.ones(B, np.float32),
    )
    assert tok[0] == int(np.argmax(want[past[0]]))
    assert abs(logp[0] - logp_of(want[past[0]], tok[0])) < 1e-3
