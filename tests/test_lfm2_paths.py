"""A model with layers of two kinds (tiny-lfm2: conv, conv, attention,
conv, conv, conv; two dense layers, then 16 experts top-4 behind a
sigmoid router with a selection bias) on every path that carries, commits,
moves or drops its per-sequence conv state, against the plain float32
reference (``perfbench/reference/lfm2_moe.py``) on seeded random weights.

What is compared is LOGITS (or the log-probability the program reports
for a token, against the reference's at that position). Tolerance 2e-4 of
the largest logit unless a test says otherwise: both sides compute in
float32 and differ in summation order only (measured: about 2e-6), and at
that level no selection flips. A conv state that is dropped, stale or one
token off moves the next positions by 1e-1 and more.
"""

import dataclasses
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import lfm2_moe
from perfbench.sut import System
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.kvtier import quantize_payload
from sutro_tpu.engine.prefixstore import PrefixStore
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import moe
from tests.test_kv_tiers import (  # noqa: F401  (mktier is a fixture)
    _batcher, _preempt_session, _reqs, _run, mktier,
)

TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-lfm2"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-lfm2-cpu.json").read_text()
)
PS, MP = 8, 16


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=20, seed=11,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine())


@pytest.fixture(scope="module")
def step(runner):
    """One decode step of given tokens through the cache, as
    ``perfbench/sut.py`` takes it: logits [B, V], the cache committed."""

    @jax.jit
    def jitted(params, cache, tok, past_len, page_table):
        logits, _, (k, v) = runner._trunk_decode(
            params, cache, tok[:, None], past_len[:, None], past_len, page_table
        )
        cache = write_kv(cache, k, v, page_table, past_len,
                         jnp.ones_like(past_len))
        return logits[:, 0].astype(jnp.float32), cache

    def run(tokens, positions, tables):
        logits, runner.cache = jitted(
            runner.params, runner.cache,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(np.atleast_2d(tables), jnp.int32),
        )
        return np.asarray(logits)

    return run


def table_of(*pages):
    t = np.zeros((MP,), np.int32)
    t[: len(pages)] = pages
    return t


def sequence(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def want(runner, seq, positions):
    return np.asarray(
        lfm2_moe.logits_at(KEYS, runner.params, seq, list(positions))
    )


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))


# -- (a) one prefill -----------------------------------------------------------

def test_prefill_alone_and_batched(runner):
    seq = sequence(0, 19)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner, seq, [18])[0]) < TOL
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    got = runner.prefill_batch(
        rows, np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    )
    for g, row in zip(got, rows):
        assert err(g, want(runner, row, [len(row) - 1])[0]) < TOL


# -- (b) prefill, then decode steps through the cache --------------------------

def test_prefill_then_eight_decode_steps_as_the_benchmark_takes_them(runner):
    sut = object.__new__(System)
    sut.ecfg, sut.engine_key = runner.ecfg, "tiny-lfm2"
    sut.engine = types.SimpleNamespace(
        _runner_cache={"tiny-lfm2": (runner, None)}
    )
    ids = np.stack([sequence(s, 27) for s in (4, 5, 6)])
    got = sut.logits_through_cache(ids, 19, 8)   # decode crosses a page end
    assert got.shape == (3, 9, MCFG.vocab_size)
    for g, seq in zip(got, ids):
        assert err(g, want(runner, seq, range(18, 27))) < TOL


# -- (c) a prompt longer than prefill_chunk ------------------------------------

def test_chunked_prefill_across_boundaries_that_are_no_page_boundaries(
    runner, step
):
    seq = sequence(7, 59)            # chunks of 20: boundaries at 20 and 40
    assert runner.ecfg.prefill_chunk % PS and len(seq) > 2 * 20
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    wanted = want(runner, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL


def test_a_chunk_that_forgets_the_state_is_caught(runner, monkeypatch):
    """The control of (c): the same prefill with the state read as
    zeros at every chunk start is two orders over the tolerance."""
    from sutro_tpu.engine import runner as runner_mod

    monkeypatch.setattr(
        runner_mod, "read_conv_state",
        lambda cache, table, start, layers, hidden: jnp.zeros(
            (MCFG.num_conv_layers, table.shape[0], 2, MCFG.hidden_size),
            jnp.float32),
    )
    forgetful = ModelRunner(
        dataclasses.replace(MCFG, name="tiny-lfm2: forgets"), engine(),
        params=runner.params,
    )
    seq = sequence(7, 59)
    got = forgetful.prefill(seq[:42], table_of(*range(1, 9)))
    assert err(got, want(runner, seq, [41])[0]) > 100 * TOL


# -- (d) a fused window against single steps -----------------------------------

def test_a_fused_window_is_its_steps_one_at_a_time(runner, step):
    prompts = [sequence(8, 13), sequence(9, 21)]
    tables = np.stack([table_of(1, 2, 3, 4, 5), table_of(6, 7, 8, 9, 10)])
    tables4 = np.concatenate([tables, np.zeros((2, MP), np.int32)])
    first = np.argmax(runner.prefill_batch(prompts, tables), axis=-1)
    lens = np.array([13, 21, 0, 0], np.int32)
    last = np.array([first[0], first[1], 0, 0], np.int32)
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    toks, logps = runner.decode_multi(
        last, lens, tables4, jax.random.PRNGKey(0), zeros, ones, 6
    )                                                   # greedy, 6 steps
    for b, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, [first[b]], toks[:, b]])
        n = len(prompt)
        ref = jax.nn.log_softmax(want(runner, seq, range(n, n + 6)), axis=-1)
        chosen = np.asarray(ref)[np.arange(6), toks[:, b]]
        # the window's reported log-probabilities are the reference's at
        # the tokens it chose, every step: the state travelled the scan
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
        # and the state the window committed carries the next step
        nxt = step([toks[-1, b]], [n + 6], tables[b])[0]
        seq2 = np.concatenate([seq, [0]])
        assert err(nxt, want(runner, seq2, [n + 6])[0]) < TOL


def test_a_speculative_window_commits_any_accepted_prefix(runner, step):
    prompt = sequence(10, 14)
    table = table_of(1, 2, 3, 4)
    tables4 = np.concatenate([table[None], np.zeros((3, MP), np.int32)])
    first = int(np.argmax(runner.prefill(prompt, table)))
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    toks, _, handle = runner.decode_window(
        np.array([first, 0, 0, 0], np.int32), np.array([14, 0, 0, 0], np.int32),
        tables4, jax.random.PRNGKey(0), zeros, ones, 4,
    )
    runner.commit_window(handle, np.array([2, 0, 0, 0], np.int32))
    # two of the window's four inputs stand; decoding goes on with a
    # token the window did not sample
    seq = np.concatenate([prompt, [first, toks[0, 0], 7, 0]])
    got = step([7], [16], table)[0]
    assert err(got, want(runner, seq, [16])[0]) < TOL


# -- (e) verify, a partial accept, then decoding on ----------------------------

@pytest.mark.parametrize(
    "accepted", [[1, 3], [5, 0]], ids=["one-and-three", "all-and-none"]
)
def test_verify_with_a_part_of_its_inputs_accepted(runner, step, accepted):
    seqs = [sequence(11, 24), sequence(12, 24)]
    starts = [15, 11]
    tables = np.stack([table_of(1, 2, 3, 4), table_of(5, 6, 7, 8)])
    runner.prefill_batch([s[:n] for s, n in zip(seqs, starts)], tables)
    tables4 = np.concatenate([tables, np.zeros((2, MP), np.int32)])
    last = np.array([seqs[0][15], seqs[1][11], 0, 0], np.int32)
    drafts = np.zeros((4, 4), np.int32)
    drafts[0], drafts[1] = seqs[0][16:20], seqs[1][12:16]
    dlens = np.array([4, 4, 0, 0], np.int32)
    past = np.array(starts + [0, 0], np.int32)
    runner.verify_candidates(
        last, drafts, dlens, np.zeros((4, 5, 2), np.int32),
        np.zeros((4, 5), np.int32), past, tables4,
    )
    # the host accepts that many of the inputs [last, d0..d3]
    runner.commit_verified(np.array(accepted + [0, 0], np.int32))
    for b in (0, 1):
        n = starts[b] + accepted[b]
        # decoding goes on from the accepted length with another token
        seq = np.concatenate([seqs[b][:n], [9, 0]])
        got = step([9], [n], tables[b])[0]
        assert err(got, want(runner, seq, [n])[0]) < TOL


def test_a_verify_that_is_never_committed_leaves_the_state_alone(runner, step):
    seq = sequence(13, 20)
    table = table_of(1, 2, 3)
    tables4 = np.concatenate([table[None], np.zeros((3, MP), np.int32)])
    runner.prefill(seq[:12], table)
    runner.verify_candidates(
        np.array([seq[12], 0, 0, 0], np.int32), np.zeros((4, 4), np.int32),
        np.array([4, 0, 0, 0], np.int32), np.zeros((4, 5, 2), np.int32),
        np.zeros((4, 5), np.int32),                    # cand_n 0: no plan
        np.array([12, 0, 0, 0], np.int32), tables4,
    )
    runner.commit_verified(np.zeros((4,), np.int32))   # nothing accepted
    got = step([seq[12]], [12], table)[0]
    assert err(got, want(runner, seq, [12])[0]) < TOL


# -- (f) pause and resume ------------------------------------------------------

def test_pages_read_out_and_written_elsewhere_resume_the_sequence(runner, step):
    seq = sequence(14, 30)
    table = table_of(1, 2, 3, 4)
    runner.prefill(seq[:19], table)                    # 19 = 2 pages + 3
    step([seq[19]], [19], table)
    payload = runner.read_pages([1, 2, 3])
    assert payload["c"].shape == (MCFG.num_conv_layers, 3, 2, MCFG.hidden_size)
    # the tiers keep the state as it is, whatever they do to K/V
    assert np.array_equal(quantize_payload(payload)["c"], payload["c"])
    runner.prefill(sequence(15, 30), table)            # the pages are reused
    moved = table_of(9, 10, 11, 12)
    runner.write_pages([9, 10, 11], payload)
    wanted = want(runner, seq, range(20, 23))
    for j, pos in enumerate(range(20, 23)):
        assert err(step([seq[pos]], [pos], moved)[0], wanted[j]) < TOL
    with pytest.raises(ValueError, match="no conv state"):
        runner.write_pages([9], {k: v[:, :1] for k, v in payload.items()
                                 if k != "c"})


def test_hibernate_and_resume_in_mid_sequence(byte_tok, mktier):
    """Scheduler level: a preempted row's pages, conv state among them,
    go to the host tier and come back into other pages; on an int8 pool
    every hop is exact, so the tokens are those of the undisturbed run."""
    r = ModelRunner(MCFG, engine(kv_quantize="int8", interactive_slots=2,
                                 prefill_chunk=64))
    _, solo = _run(_batcher(r, byte_tok),
                   _reqs(byte_tok, max_new_tokens=24, temperature=0.0))
    _, isolo = _run(_batcher(r, byte_tok),
                    _reqs(byte_tok, tails=["quick probe"], row_base=100,
                          max_new_tokens=4, temperature=0.0))
    state, bctx, got, igot, b = _preempt_session(
        r, byte_tok, mktier(PS, host_pages=256)
    )
    assert state == "completed"
    assert bctx.stats.get("resumes_upload", 0) >= 1
    assert bctx.stats.get("resumes_reprefill", 0) == 0
    assert {i: x.token_ids for i, x in got.items()} == solo
    assert {i: x.token_ids for i, x in igot.items()} == isolo


# -- (g) a second job that hits the first one's prefix -------------------------

def test_rows_that_share_a_prefix_take_its_state_from_the_shared_pages(
    runner, step
):
    prefix = sequence(16, 16)                          # two whole pages
    tails = [sequence(17, 7), sequence(18, 11)]
    runner.prefill(prefix, table_of(1, 2))
    tables = np.stack([table_of(1, 2, 3, 4), table_of(1, 2, 5, 6)])
    got = runner.prefill_batch_at(tails, tables, [16, 16])
    for b, tail in enumerate(tails):
        seq = np.concatenate([prefix, tail, [3, 0]])
        n = 16 + len(tail)
        assert err(got[b], want(runner, seq, [n - 1])[0]) < TOL
        assert err(step([3], [n], tables[b])[0], want(runner, seq, [n])[0]) < TOL


def test_a_second_job_hits_the_first_ones_prefix(byte_tok):
    r = ModelRunner(MCFG, engine(prefill_chunk=64))
    _, cold = _run(_batcher(r, byte_tok),
                   _reqs(byte_tok, row_base=0, max_new_tokens=10))
    store = PrefixStore(PS)
    _run(_batcher(r, byte_tok, store=store),
         _reqs(byte_tok, max_new_tokens=10))
    hits = store.hits
    _, warm = _run(_batcher(r, byte_tok, store=store),
                   _reqs(byte_tok, max_new_tokens=10))
    assert store.hits > hits                            # the prefix was warm
    assert warm == cold         # greedy: the same state gives the same rows


# -- (h) a slot reused by a new sequence ---------------------------------------

def test_a_new_sequence_in_used_pages_starts_from_zero_state(runner, step):
    table = table_of(1, 2, 3)
    runner.prefill(sequence(19, 23), table)
    step([5], [23], table)
    fresh = sequence(20, 20)
    got = runner.prefill(fresh[:3], table)              # inside the old page
    wanted = want(runner, fresh, [2, 3, 4])
    assert err(got, wanted[0]) < TOL
    assert err(step([fresh[3]], [3], table)[0], wanted[1]) < TOL
    assert err(step([fresh[4]], [4], table)[0], wanted[2]) < TOL


# -- the router ----------------------------------------------------------------

def _route(logits_of, bias=None, **form):
    E = logits_of.shape[-1]
    x = jnp.asarray(logits_of, jnp.float32)
    return moe._route(x, jnp.eye(E, dtype=jnp.float32), None, 4,
                      score="sigmoid", select_bias=bias, **form)


def test_a_bias_that_changes_the_chosen_set_leaves_the_scores_alone():
    logits = np.random.default_rng(0).normal(size=(64, 16)).astype(np.float32)
    bias = jnp.asarray(
        np.random.default_rng(1).normal(size=16).astype(np.float32) * 0.5
    )
    idx0, p0, *_ = _route(logits, renorm=False)
    idx1, p1, *_ = _route(logits, bias, renorm=False)
    idx0, idx1, p0, p1 = map(np.asarray, (idx0, idx1, p0, p1))
    assert (np.sort(idx0, axis=1) != np.sort(idx1, axis=1)).any()
    stayed = 0
    for t in range(64):
        for e in set(idx0[t]) & set(idx1[t]):
            # an expert both selections chose weighs its own score, bias or no
            assert p0[t][idx0[t] == e] == p1[t][idx1[t] == e]
            stayed += 1
    assert stayed > 64
    # renormalised, the bias still only chooses: the weights are the
    # chosen experts' scores over their sum
    _, pn, *_ = _route(logits, bias, renorm=True)
    np.testing.assert_allclose(
        np.asarray(pn), p1 / (p1.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-6
    )


def test_the_renormalisation_adds_1e_6_to_the_sum():
    # scores near 1e-3: the sum of four is 4e-3, and 1e-6 is 2.5e-4 of it
    logits = np.full((8, 16), -7.0, np.float32)
    logits += np.random.default_rng(2).normal(size=(8, 16)).astype(np.float32) * 0.1
    _, p, *_ = _route(logits, renorm=True)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    top = np.sort(s, axis=1)[:, -4:]
    assert abs(float(np.asarray(p).sum(axis=1).mean())
               - float((top.sum(1) / (top.sum(1) + 1e-6)).mean())) < 2e-5
    assert float(np.asarray(p).sum(axis=1).max()) < 1.0 - 1e-4


def test_softmax_routing_is_what_it_was():
    x = jnp.asarray(np.random.default_rng(3).normal(size=(32, 16)), jnp.float32)
    idx, p, *_ = moe._route(x, jnp.eye(16, dtype=jnp.float32), None, 4)
    top_logits, top_idx = jax.lax.top_k(x, 4)
    assert np.array_equal(np.asarray(idx), np.asarray(top_idx))
    assert np.array_equal(np.asarray(p),
                          np.asarray(jax.nn.softmax(top_logits, axis=-1)))
    with pytest.raises(ValueError, match="sigmoid"):
        moe._route(x, jnp.eye(16, dtype=jnp.float32), None, 4,
                   select_bias=jnp.zeros(16))


@pytest.mark.parametrize("renorm,scale", [(False, 1.0), (True, 2.5), (False, 2.5)])
def test_unnormalised_and_scaled_routing_against_the_reference(
    runner, renorm, scale
):
    cfg = dataclasses.replace(MCFG, router_renorm=renorm, router_scale=scale)
    keys = dict(KEYS, norm_topk_prob=renorm, routed_scaling_factor=scale)
    seq = sequence(21, 24)
    out, _, _ = transformer.forward(
        cfg, runner.params, jnp.asarray(seq)[None], jnp.arange(24)[None],
        jnp.asarray([24]),
    )
    wanted = np.asarray(lfm2_moe.logits_at(keys, runner.params, seq, range(24)))
    assert err(np.asarray(out[0]), wanted) < TOL
    # and the form matters: the published one is far from this one
    plain = want(runner, seq, range(24))
    assert err(plain, wanted) > 100 * TOL


# -- what the walk is made of --------------------------------------------------

def test_the_walk_scans_repeated_groups_and_counts_the_published_model():
    assert transformer.layer_groups(MODEL_CONFIGS["lfm2-24b-a2b"]) == [
        (0, 1, 2), (2, 4, 9), (38, 1, 1), (39, 1, 1)]
    cut = MODEL_CONFIGS["lfm2-24b-a2b-l10"]
    assert transformer.layer_groups(cut) == [(0, 1, 2), (2, 4, 2)]
    shapes = jax.eval_shape(
        lambda key: transformer.init_params(cut, key), jax.random.PRNGKey(0)
    )
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)
               ) == 5_267_090_176
    assert MODEL_CONFIGS["qwen3-4b"].homogeneous
    assert transformer.layer_groups(MODEL_CONFIGS["qwen3-4b"]) == [(0, 1, 36)]


def test_the_pool_spans_the_attention_layers_and_the_state_is_beside_it(runner):
    info = runner.device_info()
    assert info["pool_layers"] == info["attn_layers"] == 1
    assert info["num_layers"] == 6 and info["state_layers"] == 5
    assert runner.cache.k_pages.shape[0] == 1
    # page-major and flat: a row a page, layer by layer
    assert runner.cache.conv.shape == (runner.num_pages, 5 * 2 * 128)
    assert info["state_bytes"] == runner.cache.conv.nbytes
    per_page = runner._page_bytes_per_device()
    assert per_page == 2 * PS * 64 * 4 + 5 * 2 * 128 * 4


def test_what_the_mixed_walk_does_not_implement_it_refuses():
    with pytest.raises(NotImplementedError, match="sliding windows"):
        transformer.init_params(
            dataclasses.replace(MCFG, sliding_window=8,
                                sliding_pattern="alternate"),
            jax.random.PRNGKey(0),
        )
    with pytest.raises(ValueError, match="layer_types has"):
        transformer.init_params(
            dataclasses.replace(MCFG, num_layers=7), jax.random.PRNGKey(0)
        )
    with pytest.raises(NotImplementedError, match="several kinds"):
        ModelRunner(MCFG, engine(quantize="int8"))
