"""``tiny-solar-kda`` (gated NoPE GQA beside Kimi Delta Attention layers
whose state lives in the slot pool, a held share of the experts) on
every path that walks the layers, against the plain float32 reference
(``perfbench/reference/kda_gqa_moe.py``) on seeded random weights.

What is compared is LOGITS (or the log-probability the program reports
for a token). Tolerance 2e-4 of the largest logit: both sides compute in
float32 and differ in summation order and the chunk form's algebra
(measured about 2e-6; tests/test_solar_kda_reference.py has the reasons
and the controls).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.kvcache import (
    alloc_cache, state_bytes_per_slot, write_kv,
)
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import lowering
from tests.solar_kda_common import (
    MCFG, MP, engine, err, sequence, table_of, want,
)
from tests import window_buffers_common

TOL = 2e-4


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine())


@pytest.fixture(autouse=True)
def every_slot_free(runner):
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


# -- the shape of the thing ------------------------------------------------------

def test_the_presets_are_the_published_period_and_one_state_description():
    assert MCFG.mixers == ("attention", "kda", "kda", "kda", "attention", "kda")
    assert MCFG.ffns == ("moe",) * 6 and not MCFG.homogeneous
    assert (MCFG.state_kind, MCFG.num_state_layers) == ("kda", 4)
    assert (MCFG.state_rows, MCFG.state_inner) == (16, 64)
    assert (MCFG.state_conv_dim, MCFG.state_conv_len) == (192, 3)
    full = MODEL_CONFIGS["solar-open2-250b"]
    cut = MODEL_CONFIGS["solar-open2-250b-l8-ep16"]
    assert full.mixers.count("attention") == 12 and full.num_kda_layers == 36
    assert [i for i, m in enumerate(full.mixers) if m == "attention"] == list(
        range(0, 48, 4))
    # the walk scans the period of four
    assert transformer.layer_groups(full) == [(0, 4, 12)]
    assert transformer.layer_groups(cut) == [(0, 4, 2)]
    assert (cut.num_kda_layers, cut.num_attn_layers) == (6, 2)
    assert (cut.moe_experts, cut.moe_top_k, cut.experts_held) == (320, 8, 20)
    assert (cut.state_rows, cut.state_inner, cut.state_conv_dim) == (
        128, 8192, 24576)
    assert cut.kda_beta_scale == 2.0 and cut.kda_rank == 128
    assert cut.position_embedding == "nope" and cut.attn_gate
    # Mamba-2's description through the same keys
    granite = MODEL_CONFIGS["tiny-granite"]
    assert (granite.state_kind, granite.state_rows, granite.state_inner) == (
        "mamba", granite.mamba_state, granite.mamba_inner)
    assert MODEL_CONFIGS["tiny-dense"].state_kind is None


def test_the_slot_pool_is_keyed_by_the_state_description(runner):
    c = runner.cache
    assert c.ssm.shape == (4, 1 + 4, 16, 64)
    assert c.ssm_conv.shape == (1 + 4, 4 * 3 * 192)
    assert c.k_pages.shape[0] == 2 and c.conv is None
    assert state_bytes_per_slot(MCFG, runner.ecfg) == 4 * (16 * 64 + 3 * 192) * 4
    info = runner.device_info()
    assert info["state_kind"] == "kda" and info["state_layers"] == 4
    assert info["state_bytes_per_slot"] == state_bytes_per_slot(MCFG, runner.ecfg)
    assert runner.state_matrix_bytes(3) == 3 * 4 * 16 * 64 * 4
    layers = runner.params["layers"]
    assert set(layers) == {"attn", "kda", "moe"}
    assert layers["kda"]["w_fb"].shape == (4, 8, 64)
    assert layers["kda"]["a_log"].dtype == jnp.float32
    assert layers["moe"]["we_up"].shape[1] == MCFG.experts_held


# -- prefill: alone, batched, chunked, a suffix --------------------------------

def test_prefill_alone_and_rows_of_different_lengths_in_one_padded_batch(runner):
    seq = sequence(0, 19)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner, seq, [18])[0]) < TOL
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    got = runner.prefill_batch(rows, tables)
    for g, row in zip(got, rows):
        assert err(g, want(runner, row, [len(row) - 1])[0]) < TOL


def test_chunked_prefill_then_single_steps_and_a_suffix_prefill(runner, step):
    seq = sequence(7, 59)     # prefill chunks of 20, kda chunks of 8
    assert runner.ecfg.prefill_chunk % MCFG.kda_chunk and len(seq) > 2 * 20
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    wanted = want(runner, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL
    # a suffix: the first 24 tokens' state and pages stay, the rest again
    runner.prefill(seq[:24], table)
    got = runner.prefill_batch_at(
        [seq[24:40]], table[None], np.array([24], np.int32)
    )
    assert err(got[0], want(runner, seq, [39])[0]) < TOL


# -- fused and speculative windows, verify chunks --------------------------------

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
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
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
    seq = np.concatenate([prompt, [first, toks[0, 0], 7, 0]])
    got = step([7], [16], table)[0]
    assert err(got, want(runner, seq, [16])[0]) < TOL


@pytest.mark.parametrize(
    "accepted", [[1, 3], [5, 0]], ids=["one-and-three", "all-and-none"],
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
        n = starts[b]
        ref = jax.nn.log_softmax(want(runner, seqs[b], range(n, n + 5)), -1)
        assert np.max(np.abs(np.max(np.asarray(ref), -1) - plain_lp[b])) < 5e-4
    runner.commit_verified(np.array(accepted + [0, 0], np.int32))
    for b in (0, 1):
        n = starts[b] + accepted[b]
        seq = np.concatenate([seqs[b][:n], [9, 0]])
        got = step([9], [n], tables[b])[0]
        assert err(got, want(runner, seq, [n])[0]) < TOL

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


# -- through the scheduler: the wave, slots, spans, counters -------------------

def _counter(name, key=None):
    series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
    return sum(v for k, v in series.items() if key is None or k == key)


def test_through_the_scheduler_tokens_slots_spans_and_counters(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than a page",
               "a second", "and a third one of middling length",
               "a fourth", "a fifth that waits for a slot", "the sixth"]
    telemetry.set_enabled(True)
    before = {
        k: _counter("sutro_kda_dispatches_total", k)
        for k in ("chunked", "pending")
    }
    moved0 = _counter("sutro_kda_state_bytes_total")
    needed0 = _counter("sutro_kda_state_bytes_needed_total")
    b = ContinuousBatcher(runner, stop_ids=[])
    out = {}
    b.run(
        [GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                    max_new_tokens=10, temperature=0.0)
         for i, p in enumerate(prompts)],
        on_result=lambda r: out.__setitem__(r.row_id, r),
    )
    # six rows through a batch and a pool of four: slots released and reused
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, out[i].token_ids]).astype(np.int32)
        ref = want(runner, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(out[i].token_ids)
    assert runner.pools.slots.in_use == 0 and runner.pools.slots.total == 4
    attrs = b._tel_attrs["decode_window"]
    assert attrs["kda_state_bytes"] == runner.state_matrix_bytes(
        attrs["state_rows"])
    assert attrs["state_bytes"] > attrs["kda_state_bytes"]   # + conv columns
    for form in ("chunked", "pending"):
        assert _counter("sutro_kda_dispatches_total", form) > before[form]
    moved = _counter("sutro_kda_state_bytes_total") - moved0
    needed = _counter("sutro_kda_state_bytes_needed_total") - needed0
    # the XLA forms gather and scatter: about three times the need
    assert needed > 0 and 1.5 < moved / needed < 5.0
    forms = lowering.kda_counts()
    assert forms["chunked"] > 0 and forms["pending"] > 0


def test_rows_that_share_a_prefix_prefill_it_again_and_say_so(runner):
    """A slot's state is in no page: rows that share a prefix prefill it
    again, counted under the reason Mamba-2's slots count."""
    from sutro_tpu.engine.prefixstore import PrefixStore
    from tests.solar_kda_common import PS

    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    shell = "one shared shell of twenty-odd bytes: "
    prompts = [shell + t for t in ("alpha", "beta beta", "gamma")]
    telemetry.set_enabled(True)
    name = "sutro_state_fallback_prefill_tokens_total"

    def fallback():
        series = telemetry.REGISTRY.collect().get(name, {}).get("series", {})
        return sum(v for k, v in series.items()
                   if "prefix_without_state_snapshot" in str(k))

    before = fallback()
    b = ContinuousBatcher(runner, stop_ids=[], prefix_store=PrefixStore(PS))
    assert b._prefix_store is None            # no page holds the state
    out = {}
    b.run(
        [GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                    max_new_tokens=4, temperature=0.0)
         for i, p in enumerate(prompts)],
        on_result=lambda r: out.__setitem__(r.row_id, r),
    )
    shared = (len(tok.encode(shell)) // PS) * PS
    assert shared >= PS and len(out) == 3
    assert fallback() - before == 2 * shared
    assert b.prefill_tokens == sum(len(tok.encode(p)) for p in prompts)


# -- what is not built is refused by name ----------------------------------------

def test_what_is_not_built_is_refused_by_name():
    both = dataclasses.replace(
        MCFG, name="kda beside mamba",
        layer_types=("kda", "mamba") * 3, mamba_heads=4, mamba_head_dim=16,
        mamba_state=8, mamba_conv=4,
    )
    with pytest.raises(NotImplementedError, match="kda.*beside mamba"):
        transformer._check_mixed(both)
    with pytest.raises(ValueError, match="unknown layer kinds.*'kda'"):
        transformer._check_mixed(dataclasses.replace(
            MCFG, name="a kind nobody has", layer_types=("gla",) * 6))
    with pytest.raises(NotImplementedError, match="attn_gate"):
        transformer.init_params(
            dataclasses.replace(MODEL_CONFIGS["tiny-dense"], name="a gated "
                                "dense model", attn_gate=True),
            jax.random.PRNGKey(0), jnp.float32,
        )
    with pytest.raises(NotImplementedError, match="kv_quantize"):
        alloc_cache(MCFG, engine(kv_quantize="int8"), 9, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="quantiz|int8"):
        ModelRunner(MCFG, engine(quantize="int8"))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(NotImplementedError, match="share of each layer|mesh"):
        ModelRunner(MCFG, engine(), mesh=mesh)
