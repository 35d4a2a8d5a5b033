"""A model with Mamba-2 layers beside NoPE attention (tiny-granite:
mamba, mamba, attention, mamba, mamba, attention, mamba; 8 heads of 32
with a state of 16, a scan chunk of 8) on every path that reads,
commits, carries or drops its per-sequence state, against the plain
float32 reference (``perfbench/reference/granite_hybrid.py``, the
recurrence one token at a time) on seeded random weights.

What is compared is LOGITS (or the log-probability the program reports
for a token, against the reference's at that position). Tolerance 2e-4 of
the largest logit: both sides compute in float32 and differ in summation
order only (measured: about 4e-7). A state that is dropped, stale, one
token off or another row's moves the next positions by 1e-2 and more
(``test_the_reference_with_one_term_changed_disagrees``).
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
from perfbench.reference import granite_hybrid
from perfbench.sut import System
from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvcache import StateSlots, state_bytes_per_slot, write_kv
from sutro_tpu.engine.kvtier import KVTierPool
from sutro_tpu.engine.prefixstore import PrefixStore
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS

TOL = 2e-4
MCFG = MODEL_CONFIGS["tiny-granite"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-granite-cpu.json").read_text()
)
from tests import window_buffers_common
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


@pytest.fixture(autouse=True)
def every_slot_free(runner):
    """Each test starts sequences at pages of its own choosing; the
    prefill entry points bind what no scheduler bound."""
    runner.pools.reset()


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


def want(runner, seq, positions, keys=KEYS, params=None, **kw):
    return np.asarray(granite_hybrid.logits_at(
        keys, runner.params if params is None else params, seq,
        list(positions), **kw
    ))


def err(got, wanted):
    return float(np.max(correctness.position_errors(got, wanted)))


# -- (a) one batched prefill; (g) rows of different lengths in one batch -------

def test_prefill_alone_and_rows_of_different_lengths_in_one_padded_batch(runner):
    seq = sequence(0, 19)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner, seq, [18])[0]) < TOL
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    got = runner.prefill_batch(rows, tables)
    for g, row in zip(got, rows):
        assert err(g, want(runner, row, [len(row) - 1])[0]) < TOL


def test_the_state_after_a_padded_row_is_the_state_after_its_own_tokens(
    runner, step
):
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    runner.prefill_batch(rows, tables)       # padded to 32: 15-23 pad tokens
    got = step([5, 6, 7], [9, 17, 12], tables)
    for g, row, tok in zip(got, rows, (5, 6, 7)):
        seq = np.concatenate([row, [tok]])
        assert err(g, want(runner, seq, [len(row)])[0]) < TOL


# -- (c) prefill, then single steps across a page boundary ---------------------

def test_prefill_then_eight_decode_steps_as_the_benchmark_takes_them(runner):
    sut = object.__new__(System)
    sut.ecfg, sut.engine_key = runner.ecfg, "tiny-granite"
    sut.engine = types.SimpleNamespace(
        _runner_cache={"tiny-granite": (runner, None)}
    )
    ids = np.stack([sequence(s, 27) for s in (4, 5, 6)])
    got = sut.logits_through_cache(ids, 19, 8)   # decode crosses a page end
    assert got.shape == (3, 9, MCFG.vocab_size)
    for g, seq in zip(got, ids):
        assert err(g, want(runner, seq, range(18, 27))) < TOL


# -- (b) a prompt longer than prefill_chunk ------------------------------------

def test_chunked_prefill_across_chunks_that_are_no_multiple_of_the_scan_chunk(
    runner, step
):
    seq = sequence(7, 59)     # prefill chunks of 20, scan chunks of 8
    assert runner.ecfg.prefill_chunk % MCFG.mamba_chunk and len(seq) > 2 * 20
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    wanted = want(runner, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL


def test_a_chunk_that_forgets_the_state_is_caught(runner, monkeypatch):
    """The control of (b): the same prefill with every chunk read as the
    start of a sequence is far over the tolerance."""
    from sutro_tpu.engine import runner as runner_mod

    real = runner_mod.read_state

    def forgetful_read(cache, table, start, layers, conv_dim):
        return real(cache, table, jnp.zeros_like(start), layers, conv_dim)

    monkeypatch.setattr(runner_mod, "read_state", forgetful_read)
    forgetful = ModelRunner(
        dataclasses.replace(MCFG, name="tiny-granite: forgets"), engine(),
        params=runner.params,
    )
    seq = sequence(7, 59)
    got = forgetful.prefill(seq[:42], table_of(*range(1, 9)))
    assert err(got, want(runner, seq, [41])[0]) > 50 * TOL


# -- (d) a fused window against the reference ----------------------------------

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
        # the tokens it chose, every step: each step saw the tokens
        # before it in the window, though none was committed yet
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
        # and the state the window committed carries the next step
        nxt = step([toks[-1, b]], [n + 6], tables[b])[0]
        seq2 = np.concatenate([seq, [0]])
        assert err(nxt, want(runner, seq2, [n + 6])[0]) < TOL


def test_under_the_kernels_a_window_commits_the_tokens_it_commits_without(
    monkeypatch,
):
    """``use_pallas`` sends every mamba layer's read of the committed
    state, each step of a fused window, to ops/pallas_ssm.py
    (interpreted here) and nothing the window commits changes."""
    from tests.test_pallas_ssm import window_with_and_without_the_kernel

    window_with_and_without_the_kernel(
        monkeypatch,
        lambda use_pallas: ModelRunner(MCFG, engine(use_pallas=use_pallas)),
        [sequence(8, 13), sequence(9, 21)],
        np.stack([table_of(1, 2, 3, 4, 5), table_of(6, 7, 8, 9, 10)]),
    )


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
    seq = np.concatenate([prompt, [first, toks[0, 0], 7, 0]])
    got = step([7], [16], table)[0]
    assert err(got, want(runner, seq, [16])[0]) < TOL

# -- the fused window's state buffers: step-major, read where they lie ------------

@pytest.mark.parametrize("q0", [0, 3, 7])
def test_a_windows_step_is_the_chunk_form_from_the_same_state(runner, q0):
    """Step ``q0`` of a window reads the ``q0`` earlier tokens from the
    buffers (NaN at and past it) and keeps its own out of them."""
    window_buffers_common.a_windows_step_is_the_chunk_form(
        MCFG, runner.params, q0
    )


def test_a_fused_window_of_eight_is_eight_single_steps(runner, step):
    window_buffers_common.a_window_is_its_steps(runner, step, TOL)


@pytest.mark.parametrize("accepted", [0, 3, 8])
def test_a_speculative_window_of_eight_commits_what_its_accepted_steps_would(
    runner, step, accepted
):
    window_buffers_common.a_window_is_its_steps(runner, step, TOL, accepted)


# -- (e) verify with none, some and all of its inputs accepted -----------------

@pytest.mark.parametrize(
    "accepted", [[1, 3], [5, 0], [0, 5], [2, 2]],
    ids=["one-and-three", "all-and-none", "none-and-all", "two-and-two"],
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
    _, _, plain, plain_lp = runner.verify_candidates(
        last, drafts, dlens, np.zeros((4, 5, 2), np.int32),
        np.zeros((4, 5), np.int32), past, tables4,
    )
    for b in (0, 1):
        # the verify forward's own logits: every input saw the ones before
        n = starts[b]
        ref = jax.nn.log_softmax(want(runner, seqs[b], range(n, n + 5)), -1)
        assert np.max(np.abs(np.max(np.asarray(ref), -1) - plain_lp[b])) < 5e-4
    runner.commit_verified(np.array(accepted + [0, 0], np.int32))
    for b in (0, 1):
        n = starts[b] + accepted[b]
        seq = np.concatenate([seqs[b][:n], [9, 0]])
        got = step([9], [n], tables[b])[0]
        assert err(got, want(runner, seq, [n])[0]) < TOL


# -- (h) a freed slot taken by a new row, with no reset ------------------------

def test_a_new_sequence_in_a_used_slot_and_used_pages_starts_from_zero(
    runner, step
):
    table = table_of(1, 2, 3)
    runner.prefill(sequence(19, 23), table)
    step([5], [23], table)
    slot = runner.pools.slots.slot_of(1)
    assert float(jnp.abs(runner.cache.ssm[:, slot]).max()) > 0
    # the same pages again: the sequence keeps the slot, not the state
    fresh = sequence(20, 20)
    got = runner.prefill(fresh[:3], table)
    assert runner.pools.slots.slot_of(1) == slot
    wanted = want(runner, fresh, [2, 3, 4])
    assert err(got, wanted[0]) < TOL
    assert err(step([fresh[3]], [3], table)[0], wanted[1]) < TOL
    # the slot freed and taken through OTHER pages: nothing was reset
    runner.pools.release([1])
    other = table_of(9, 10, 11)
    runner.pools.bind_first([9])
    assert runner.pools.slots.slot_of(9) == slot
    got = step([fresh[0]], [0], other)[0]     # decoding from position 0
    assert err(got, want(runner, fresh, [0])[0]) < TOL
    assert err(step([fresh[1]], [1], other)[0], want(runner, fresh, [1])[0]) < TOL
    runner.pools.release([9])


def test_two_sequences_in_one_batch_keep_their_own_slots(runner, step):
    a, b = sequence(21, 30), sequence(22, 30)
    tables = np.stack([table_of(1, 2, 3, 4), table_of(5, 6, 7, 8)])
    runner.prefill_batch([a[:7], b[:15]], tables)
    for j in range(3):          # row 0 crosses into page 2, row 1 into 7
        got = step([a[7 + j], b[15 + j]], [7 + j, 15 + j], tables)
        assert err(got[0], want(runner, a, [7 + j])[0]) < TOL
        assert err(got[1], want(runner, b, [15 + j])[0]) < TOL
    # the rows swap their places in the batch: the state is found from
    # the pages, not from the row
    got = step([b[18], a[10]], [18, 10], tables[::-1])
    assert err(got[0], want(runner, b, [18])[0]) < TOL
    assert err(got[1], want(runner, a, [10])[0]) < TOL


# -- (f) pause/resume, hibernation, prefix hits: exact, or counted -------------

def _reqs(tok, prompts, **kw):
    return [
        GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32), **kw)
        for i, p in enumerate(prompts)
    ]


def _run(batcher, reqs):
    out = {}
    batcher.run(reqs, on_result=lambda r: out.__setitem__(r.row_id, r))
    return {i: r.token_ids for i, r in out.items()}


def _fallback(reason):
    series = telemetry.REGISTRY.collect().get(
        "sutro_state_fallback_prefill_tokens_total", {}
    ).get("series", {})
    return sum(v for k, v in series.items() if reason in str(k))


def test_through_the_scheduler_greedy_tokens_are_the_references(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than a page",
               "a second", "and a third one of middling length"]
    b = ContinuousBatcher(runner, stop_ids=[])
    got = _run(b, _reqs(tok, prompts, max_new_tokens=10, temperature=0.0))
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, got[i]]).astype(np.int32)
        ref = want(runner, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(got[i])
    # every row gave its slot back
    assert runner.pools.slots.in_use == 0


def test_rows_that_share_a_prefix_prefill_it_again_and_say_so(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    shell = "one shared shell of twenty-odd bytes: "
    prompts = [shell + t for t in ("alpha", "beta beta", "gamma")]
    telemetry.set_enabled(True)
    before = _fallback("prefix_without_state_snapshot")
    cold = _run(ContinuousBatcher(runner, stop_ids=[]),
                _reqs(tok, prompts[:1], max_new_tokens=6, temperature=0.0))
    b = ContinuousBatcher(runner, stop_ids=[], prefix_store=PrefixStore(PS))
    assert b._prefix_store is None            # no page holds the state
    got = _run(b, _reqs(tok, prompts, max_new_tokens=6, temperature=0.0))
    shared = (len(tok.encode(shell)) // PS) * PS
    assert shared >= PS
    assert _fallback("prefix_without_state_snapshot") - before == 2 * shared
    assert got[0] == cold[0]                  # and none started from a wrong state
    assert b.prefill_tokens == sum(len(tok.encode(p)) for p in prompts)


def test_a_row_that_would_hibernate_regenerates_and_says_so(runner, tmp_path):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    tier = KVTierPool(page_size=PS, host_pages=64)
    telemetry.set_enabled(True)
    b = ContinuousBatcher(runner, stop_ids=[], kv_tier=tier)
    assert b._kv_tier is None and not b._can_hibernate
    req = _reqs(tok, ["a row that is preempted in mid-sequence"],
                max_new_tokens=4, temperature=0.0)[0]
    from sutro_tpu.engine.scheduler import _Slot
    i, pages, _ = b._reserve(req, types.SimpleNamespace(prefix=None))
    assert runner.pools.slots.in_use == 1     # bound with the reservation
    b.slots[i] = _Slot(req=req, pages=list(pages), pos=21, last_token=1,
                       job=None, shared_n=0)
    before = _fallback("hibernate_without_slot_state")
    assert b._hibernate_slot(i) is False      # the caller suspends plainly
    assert _fallback("hibernate_without_slot_state") - before == 21
    with pytest.raises(ValueError, match="a slot a sequence"):
        runner.write_pages([1], runner.read_pages([1]))
    b._unreserve(i, pages)
    assert runner.pools.slots.in_use == 0


def test_admission_waits_for_a_state_slot_as_it_waits_for_pages():
    small = ModelRunner(MCFG, engine(decode_batch_size=4), num_pages=1 + 3)
    assert small.pools.slots.total == 3       # never more than pages
    assert small.cache.ssm.shape[1] == 4      # and the garbage slot
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    b = ContinuousBatcher(small, stop_ids=[])
    got = _run(b, _reqs(tok, ["a", "b", "c", "d", "e"], max_new_tokens=3,
                        temperature=0.0))
    assert sorted(got) == [0, 1, 2, 3, 4] and small.pools.slots.in_use == 0
    # with every slot taken a row is not admitted, pages or no pages
    small.pools.bind_first([1, 2, 3])
    ctx = types.SimpleNamespace(prefix=None)
    assert b._reserve(_reqs(tok, ["x"], max_new_tokens=2)[0], ctx) is None
    small.pools.reset()


# -- the check's teeth: the reference with one term changed --------------------

def _params_with(runner, **leaves):
    p = jax.tree_util.tree_map(lambda a: a, runner.params)
    mamba = dict(p["layers"]["mamba"])
    for name, fn in leaves.items():
        mamba[name] = fn(mamba[name])
    return {**p, "layers": {**p["layers"], "mamba": mamba}}


CHANGED = {
    "decay-dropped": dict(params=dict(a_log=lambda a: jnp.full_like(a, -40.0))),
    "skip-dropped": dict(params=dict(d_skip=jnp.zeros_like)),
    "conv-bias-dropped": dict(params=dict(b_conv=jnp.zeros_like)),
    "gate-after-the-norm": dict(kw=dict(gate_after_norm=True)),
    "attention-multiplier-0.125": dict(keys=dict(attention_multiplier=0.125)),
    "rotary-embedding-on": dict(keys=dict(position_embedding_type="rope")),
    "embedding-multiplier-1": dict(keys=dict(embedding_multiplier=1)),
    "residual-multiplier-1": dict(keys=dict(residual_multiplier=1)),
    "logits-scaling-1": dict(keys=dict(logits_scaling=1)),
}


@pytest.mark.parametrize("change", sorted(CHANGED))
def test_the_reference_with_one_term_changed_disagrees(runner, change):
    spec = CHANGED[change]
    seq = sequence(30, 96)
    out, _, _ = transformer.forward(
        MCFG, runner.params, jnp.asarray(seq)[None], jnp.arange(96)[None],
        jnp.asarray([96]),
    )
    positions = range(48, 96)
    got = np.asarray(out[0])[48:]
    assert err(got, want(runner, seq, positions)) < TOL
    params = _params_with(runner, **spec["params"]) if "params" in spec else None
    other = want(runner, seq, positions, keys=dict(KEYS, **spec.get("keys", {})),
                 params=params, **spec.get("kw", {}))
    # float32's tolerance is 0.002 (reference/tolerance.json)
    assert err(got, other) > 0.002


# -- the chunked scan alone, against the recurrence ----------------------------

@pytest.mark.parametrize("T,chunk", [(37, 8), (64, 16), (5, 8), (1, 8)])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    rng = np.random.default_rng(T)
    B, Hm, P, N = 2, 3, 4, 5
    x = rng.normal(size=(B, T, Hm, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, size=(B, T, Hm)).astype(np.float32)
    dt[1, T // 2:] = 0.0                      # row 1 is padded from there
    A = -rng.uniform(0.5, 4.0, size=(Hm,)).astype(np.float32)
    Bm = rng.normal(size=(B, T, Hm, N)).astype(np.float32)   # a group a head
    Cm = rng.normal(size=(B, T, Hm, N)).astype(np.float32)
    S0 = rng.normal(size=(B, N, Hm, P)).astype(np.float32)
    y, S = transformer.ssd_chunked(
        *map(jnp.asarray, (x, dt, dt * A, Bm, Cm, S0)), chunk
    )
    S_ref = S0.astype(np.float64).copy()
    for t in range(T):
        a = np.exp(dt[:, t] * A)                                  # [B, Hm]
        S_ref = a[:, None, :, None] * S_ref + np.einsum(
            "bh,bhp,bhn->bnhp", dt[:, t], x[:, t], Bm[:, t]
        )
        y_t = np.einsum("bnhp,bhn->bhp", S_ref, Cm[:, t])
        assert np.allclose(np.asarray(y[:, t]), y_t, atol=2e-4), t
        if t == T // 2 - 1:
            half = S_ref[1].copy()
    assert np.allclose(np.asarray(S), S_ref, atol=2e-4)
    if T > 1:   # the padded row's state is the state after its own tokens
        assert np.allclose(np.asarray(S)[1], half, atol=2e-4)


# -- what the walk and the pools are made of -----------------------------------

def test_the_walk_scans_the_published_forty_layers_as_one_group_of_ten():
    big = MODEL_CONFIGS["granite-4.0-h-micro"]
    assert transformer.layer_groups(big) == [(0, 10, 4)]
    assert big.layer_types.count("attention") == 4
    assert [i for i, k in enumerate(big.layer_types) if k == "attention"] == [
        5, 15, 25, 35]
    assert transformer.layer_groups(MCFG) == [(0, 3, 2), (6, 1, 1)]
    shapes = jax.eval_shape(
        lambda key: transformer.init_params(big, key), jax.random.PRNGKey(0)
    )
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)
               ) == 3_191_396_096
    assert not big.homogeneous and MODEL_CONFIGS["qwen3-4b"].homogeneous


def test_the_state_is_a_slot_a_sequence_beside_the_pages(runner):
    info = runner.device_info()
    assert info["pool_layers"] == info["attn_layers"] == 2
    assert info["num_layers"] == 7 and info["state_layers"] == 5
    c = runner.cache
    assert c.conv is None
    # a slot a row of the decode batch, and the garbage slot
    assert c.ssm.shape == (5, 1 + 4, 16, 8 * 32)
    assert c.ssm_conv.shape == (1 + 4, 5 * 3 * (8 * 32 + 2 * 16))
    assert c.state_slot.shape == (runner.num_pages,)
    assert info["state_bytes"] == c.ssm.nbytes + c.ssm_conv.nbytes
    assert info["state_slots"] == 5
    per_slot = state_bytes_per_slot(MCFG, runner.ecfg)
    assert per_slot * 5 == info["state_bytes"]
    assert runner.state_step_bytes(3) == 3 * per_slot
    # the pages carry K/V alone
    assert runner._page_bytes_per_device() == (
        2 * 2 * PS * 64 * 4
    )


def test_the_slot_allocator():
    slots = StateSlots(3)
    assert (slots.total, slots.free_count, slots.in_use) == (3, 3, 0)
    assert slots.bind(7) == (1, True) and slots.bind(7) == (1, False)
    assert slots.bind(9) == (2, True) and slots.bind(4) == (3, True)
    with pytest.raises(MemoryError):
        slots.bind(5)
    slots.release(9)
    slots.release(9)                          # twice is once
    assert slots.free_count == 1 and slots.slot_of(9) is None
    assert slots.bind(5) == (2, True)
    slots.reset()
    assert slots.free_count == 3 and slots.slot_of(7) is None


def test_the_other_families_programs_are_what_they_were():
    """The new fields' defaults switch nothing on: no multiplier, rotary
    embedding, 1/sqrt(head_dim), no mamba stack."""
    for name in ("qwen3-4b", "lfm2-24b-a2b-l10", "tiny-dense", "tiny-lfm2"):
        m = MODEL_CONFIGS[name]
        assert (m.embedding_multiplier, m.residual_multiplier,
                m.logits_scaling, m.attention_multiplier,
                m.position_embedding, m.num_mamba_layers) == (
            1.0, 1.0, 1.0, None, "rope", 0)
    r = ModelRunner(MODEL_CONFIGS["tiny-lfm2"], engine())
    assert r.pools.slots is None and r.cache.ssm is None
    assert r.cache.conv is not None and r.state_step_bytes(4) == 0


def test_what_the_mamba_walk_does_not_implement_it_refuses():
    with pytest.raises(ValueError, match="mamba layers need"):
        transformer.init_params(
            dataclasses.replace(MCFG, mamba_conv=1), jax.random.PRNGKey(0)
        )
    with pytest.raises(ValueError, match="unknown layer kinds"):
        transformer.init_params(
            dataclasses.replace(
                MCFG, layer_types=("mamba",) * 6 + ("linear_attention",)
            ),
            jax.random.PRNGKey(0),
        )
    with pytest.raises(NotImplementedError, match="num_local_experts"):
        granite_hybrid.logits_at(dict(KEYS, num_local_experts=8), {}, [1], [0])
