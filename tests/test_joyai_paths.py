"""A model whose every layer is latent attention over ONE latent page
pool and whose routed layers hold one rank's share of the experts
(tiny-joyai: a leading dense layer, then three routed layers of 16
experts top-4 of which 4 are held, a shared expert; query rank 24, a
latent row of 40 + 8, heads of 16 + 8 and 20) on every path that walks
the layers, against the plain float32 reference
(``perfbench/reference/mla_moe.py``: the EXPANDED form at every position
of a full causal forward, no cache, the experts one at a time) on seeded
random weights.

What is compared is LOGITS (or the log-probability the program reports
for a token, against the reference's at that position). Tolerance 2e-4
of the largest logit: both sides compute in float32; the system's
ABSORBED form (every path over the latent pages) multiplies the same
matrices in another order (measured: about 1e-6). bfloat16 PARAMETERS
under the same float32 program read 5e-3 and more, and the reference
with one term changed 1e-2 and more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.kvcache import write_kv
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.joyai_common import (
    KEYS, MCFG, MP, TOL, engine, err, sequence, system_of, table_of, want,
)


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine(), num_pages=24)


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


def _latent(form):
    series = telemetry.REGISTRY.collect().get(
        "sutro_latent_attention_dispatches_total", {}
    ).get("series", {})
    return series.get(form, 0.0)


# -- the shape of the thing ------------------------------------------------------

def test_the_preset_is_latent_everywhere_with_widths_unlike_each_other():
    assert not MCFG.homogeneous and not MCFG.one_sublayer
    assert MCFG.mixers == ("mla",) * 4
    assert MCFG.ffns == ("dense", "moe", "moe", "moe")
    assert transformer.layer_groups(MCFG) == [(0, 1, 1), (1, 1, 3)]
    widths = [MCFG.q_lora_rank, MCFG.kv_lora_rank, MCFG.qk_nope_head_dim,
              MCFG.qk_rope_head_dim, MCFG.v_head_dim, MCFG.latent_width,
              MCFG.num_kv_heads * MCFG.head_dim]
    assert len(set(widths)) == len(widths)       # nothing passes by chance
    assert MCFG.head_dim == MCFG.qk_rope_head_dim    # the file's head_dim
    assert MCFG.latent_width == 48 != MCFG.num_kv_heads * MCFG.head_dim
    assert MCFG.page_width == 128
    assert (MCFG.moe_experts, MCFG.moe_top_k, MCFG.experts_held) == (16, 4, 4)
    for name, held in (("joyai-llm-flash", 256), ("joyai-llm-flash-ep16", 16)):
        cfg = MODEL_CONFIGS[name]
        assert cfg.mixers == ("mla",) * 40 and cfg.latent_width == 576
        assert cfg.page_width == 640       # whole tiles of 128 lanes
        assert cfg.ffns == ("dense",) + ("moe",) * 39
        assert cfg.experts_held == held and cfg.moe_experts == 256
        # the leading dense layer alone, then ONE scan of 39
        assert transformer.layer_groups(cfg) == [(0, 1, 1), (1, 1, 39)]
        assert (cfg.head_dim, cfg.num_kv_heads) == (64, 32)   # the file's


def test_the_stacks_hold_the_latent_projections_and_the_held_experts(runner):
    mla = runner.params["layers"]["mla"]
    assert set(mla) == {"attn_norm", "w_qa", "q_norm", "w_qb", "w_kva",
                        "kv_norm", "w_kvb", "wo"}
    assert mla["w_kva"].shape == (4, 128, 48)
    assert mla["w_qb"].shape == (4, 24, 4 * 24)
    assert mla["w_kvb"].shape == (4, 40, 4 * 36)
    assert mla["wo"].shape == (4, 4 * 20, 128)
    moe = runner.params["layers"]["moe"]
    assert moe["router"].shape == (3, 128, 16)        # as wide as published
    assert moe["we_gate"].shape == (3, 4, 128, 48)    # the held experts
    assert moe["shared_up"].shape == (3, 128, 48)
    assert runner.params["layers"]["dense"]["w_up"].shape == (1, 128, 256)


# -- (a) prefill: the expanded form ---------------------------------------------

def test_prefill_alone_and_rows_of_different_lengths_in_one_padded_batch(runner):
    telemetry.set_enabled(True)
    e0, a0 = _latent("expanded"), _latent("absorbed")
    seq = sequence(0, 19)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner.params, seq, [18])[0]) < TOL
    rows = [sequence(1, 9), sequence(2, 17), sequence(3, 12)]
    tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(7, 8)])
    got = runner.prefill_batch(rows, tables)
    for g, row in zip(got, rows):
        assert err(g, want(runner.params, row, [len(row) - 1])[0]) < TOL
    # a chunk with no past: expanded, and nothing absorbed
    assert _latent("expanded") - e0 == 2 and _latent("absorbed") == a0


def test_what_a_token_leaves_in_the_pool_is_its_normed_latent_and_rotated_key(
    runner
):
    """The pool's rows after a prefill are the reference's ``(c_kv,
    k_pe)`` of layer 0 at each position (the layer whose input is the
    embedding, so that no earlier layer's rounding enters)."""
    from perfbench.reference import mla_moe
    from perfbench.reference.qwen3_dense import _rms, layer_weight

    seq = sequence(21, 13)
    runner.prefill(seq, table_of(5, 6))
    d = mla_moe.dims_of(KEYS)
    w = layer_weight(runner.params["layers"]["mla"], 0)
    h = runner.params["embed"][seq].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        c_kv, k_pe = mla_moe.latents(
            d, w, _rms(h, w("attn_norm"), d["eps"]), jnp.arange(13)
        )
    rows = np.asarray(runner.cache.k_pages[0, [5, 6]]).reshape(16, 128)[:13]
    assert np.abs(rows[:, :40] - np.asarray(c_kv)).max() < 1e-5
    assert np.abs(rows[:, 40:48] - np.asarray(k_pe)).max() < 1e-5
    assert not rows[:, 48:].any()          # the pad to whole lane tiles


# -- (b) prefill, then single steps: the absorbed form ---------------------------

def test_prefill_then_eight_decode_steps_as_the_benchmark_takes_them(runner):
    ids = np.stack([sequence(s, 27) for s in (4, 5, 6)])
    got = system_of(runner).logits_through_cache(ids, 19, 8)
    assert got.shape == (3, 9, MCFG.vocab_size)
    for g, seq in zip(got, ids):
        assert err(g, want(runner.params, seq, range(18, 27))) < TOL


def test_bfloat16_parameters_under_a_float32_configuration_fail(runner):
    """The tolerance's teeth: the same float32 program on parameters
    rounded to bfloat16 is over it, against the reference on the
    parameters as they were."""
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
        if a.dtype == jnp.float32 and a.ndim >= 2 else a,
        runner.params,
    )
    low = ModelRunner(
        dataclasses.replace(MCFG, name="tiny-joyai: rounded"), engine(),
        params=rounded, num_pages=24,
    )
    ids = np.stack([sequence(s, 27) for s in (4, 5, 6)])
    got = system_of(low).logits_through_cache(ids, 19, 8)
    errs = [err(g, want(runner.params, seq, range(18, 27)))
            for g, seq in zip(got, ids)]
    assert min(errs) > 5 * TOL


# -- (c) chunks over a latent past ------------------------------------------------

def test_chunked_prefill_reads_the_earlier_chunks_latent_rows(runner, step):
    telemetry.set_enabled(True)
    e0, a0 = _latent("expanded"), _latent("absorbed")
    seq = sequence(7, 59)     # prefill chunks of 20
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    assert (_latent("expanded") - e0, _latent("absorbed") - a0) == (0, 3)
    wanted = want(runner.params, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL


def test_a_suffix_prefill_over_pages_another_dispatch_wrote(runner, step):
    seqs = [sequence(13, 30), sequence(14, 26)]
    tables = np.stack([table_of(1, 2, 3, 4), table_of(5, 6, 7, 8)])
    runner.prefill_batch([s[:16] for s in seqs], tables)
    got = runner.prefill_batch_at(
        [seqs[0][16:29], seqs[1][16:25]], tables, [16, 16]
    )
    assert err(got[0], want(runner.params, seqs[0], [28])[0]) < TOL
    assert err(got[1], want(runner.params, seqs[1], [24])[0]) < TOL
    nxt = step([seqs[0][29]], [29], tables[0])[0]
    assert err(nxt, want(runner.params, seqs[0], [29])[0]) < TOL


# -- (d) fused and speculative windows --------------------------------------------

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
    stats = runner.take_route_stats()
    assert stats.shape == (6, 6)
    assert np.all(stats[:, 4] == MCFG.experts_held)
    per_step = 4 * MCFG.moe_top_k * MCFG.ffns.count("moe")
    assert np.all(stats[:, 3] + stats[:, 5] == per_step)
    for b, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, [first[b]], toks[:, b]])
        n = len(prompt)
        ref = jax.nn.log_softmax(
            want(runner.params, seq, range(n, n + 6)), axis=-1)
        chosen = np.asarray(ref)[np.arange(6), toks[:, b]]
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
        # the window's rows were committed: a step after it reads them
        nxt = step([toks[-1, b]], [n + 6], tables[b])[0]
        seq2 = np.concatenate([seq, [0]])
        assert err(nxt, want(runner.params, seq2, [n + 6])[0]) < TOL


@pytest.mark.parametrize("accepted", [0, 2, 4])
def test_a_speculative_window_commits_any_accepted_prefix(
    runner, step, accepted
):
    prompt = sequence(10, 14)
    table = table_of(1, 2, 3, 4)
    tables4 = np.concatenate([table[None], np.zeros((3, MP), np.int32)])
    first = int(np.argmax(runner.prefill(prompt, table)))
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    toks, _, handle = runner.decode_window(
        np.array([first, 0, 0, 0], np.int32), np.array([14, 0, 0, 0], np.int32),
        tables4, jax.random.PRNGKey(0), zeros, ones, 4,
    )
    runner.commit_window(handle, np.array([accepted, 0, 0, 0], np.int32))
    kept = [first] + [int(t) for t in toks[:accepted - 1, 0]] if accepted else []
    seq = np.concatenate([prompt, kept, [7, 0]]).astype(np.int32)
    n = 14 + accepted
    if not accepted:
        # nothing committed: the row steps from its prompt again
        seq = np.concatenate([prompt, [7, 0]]).astype(np.int32)
    got = step([7], [n], table)[0]
    assert err(got, want(runner.params, seq, [n])[0]) < TOL


# -- (e) a verify chunk over a latent past ----------------------------------------

@pytest.mark.parametrize(
    "accepted", [[1, 3], [5, 0], [2, 2]],
    ids=["one-and-three", "all-and-none", "two-and-two"],
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
        ref = jax.nn.log_softmax(
            want(runner.params, seqs[b], range(n, n + 5)), -1)
        assert np.max(np.abs(np.max(np.asarray(ref), -1) - plain_lp[b])) < 5e-4
    assert not runner.has_state       # nothing to commit beside the rows
    runner.commit_verified(np.array(accepted + [0, 0], np.int32))
    for b in (0, 1):
        n = starts[b] + accepted[b]
        seq = np.concatenate([seqs[b][:n], [9, 0]])
        got = step([9], [n], tables[b])[0]
        assert err(got, want(runner.params, seq, [n])[0]) < TOL


# -- through the scheduler: tokens, spans, counters --------------------------------

def _reqs(tok, prompts, **kw):
    return [
        GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32), **kw)
        for i, p in enumerate(prompts)
    ]


def test_through_the_scheduler_greedy_tokens_are_the_references(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than a page",
               "a second", "and a third one of middling length"]
    telemetry.set_enabled(True)
    e0, a0 = _latent("expanded"), _latent("absorbed")
    b = ContinuousBatcher(runner, stop_ids=[])
    out = {}
    b.run(_reqs(tok, prompts, max_new_tokens=10, temperature=0.0),
          on_result=lambda r: out.__setitem__(r.row_id, r))
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, out[i].token_ids]).astype(np.int32)
        ref = want(runner.params, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(out[i].token_ids)
    # the prompts went in expanded, every decode dispatch absorbed
    assert _latent("expanded") - e0 >= 1 and _latent("absorbed") - a0 >= 1
    attrs = b._route_attrs["decode_window"]
    assert attrs["experts_held"] == MCFG.experts_held
    assert attrs["expert_rows_held"] > 0 and attrs["expert_rows_elsewhere"] > 0


# -- the share through the whole model ----------------------------------------------

def _uncut():
    """tiny-joyai holding EVERY expert, its parameters, and the
    reference's keys for it."""
    cfg = dataclasses.replace(MCFG, name="tiny-joyai: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    keys = dict(KEYS, n_routed_experts=cfg.moe_experts)
    return cfg, params, keys


@pytest.mark.parametrize("rank", [0, 1, 2, 3])
def test_a_share_through_the_whole_model_is_the_references_share(rank):
    """The system told its rank's share against the reference given the
    same share of the SAME uncut weights; another rank's experts
    disagree."""
    cfg, params, keys = _uncut()
    quarter = cfg.moe_experts // 4
    first = rank * quarter
    seq = sequence(30, 21)
    moe_l = dict(params["layers"]["moe"])
    for name in ("we_gate", "we_up", "we_down"):
        moe_l[name] = moe_l[name][:, first : first + quarter]
    cut = dict(params, layers=dict(params["layers"], moe=moe_l))
    share_cfg = dataclasses.replace(
        cfg, name=f"tiny-joyai: rank {rank}", moe_experts_held=quarter,
        moe_first_expert=first,
    )
    r = ModelRunner(share_cfg, engine(), params=cut, num_pages=8)
    got = r.prefill(seq, table_of(1, 2, 3))
    mine = want(params, seq, [20], keys=keys, experts=(first, quarter))[0]
    other = want(
        params, seq, [20], keys=keys,
        experts=((first + quarter) % cfg.moe_experts, quarter),
    )[0]
    assert err(got, mine) < TOL
    assert err(got, other) > 50 * TOL


def test_the_reference_with_one_term_changed_disagrees(runner):
    """The check's teeth: drop the shared expert, turn the rotary pairs
    the other way, or take another rank's experts, and the reference is
    another model."""
    seq = sequence(40, 23)
    got = runner.prefill(seq, table_of(1, 2, 3))
    assert err(got, want(runner.params, seq, [22])[0]) < TOL
    assert err(got, want(runner.params, seq, [22], shared=False)[0]) > 50 * TOL
    assert err(
        got, want(runner.params, seq, [22], rotary="half_split")[0]
    ) > 50 * TOL
