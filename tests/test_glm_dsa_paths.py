"""A model whose every layer is latent attention UNDER AN INDEXER'S
SELECTION (tiny-glm-dsa: a leading dense layer, then three routed layers
of 16 experts top-4 of which 4 are held; query rank 28, a latent row of
36 + 8, heads of 12 + 8 and 20, an indexer of 3 heads of 24 that keeps 8
positions) on every path that walks the layers, against the plain
float32 reference (``perfbench/reference/dsa_moe.py``: the expanded form
at every position of a full causal forward under the selection's mask,
no cache) on seeded random weights. Every prompt here is 13-60 tokens,
so the selection of 8 bites at almost every query.

What is compared is LOGITS (or the log-probability the program reports
for a token). Tolerance 2e-4 of the largest logit: both sides compute in
float32 and differ in summation order (measured: about 2e-6); a
selection that differed in ONE position reads 1e-2 and more, the
reference attending densely or to the wrong rows 0.5 and more.
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
from sutro_tpu.ops import lowering, sparse_attention
from tests.glm_dsa_common import (
    KEYS, MCFG, MP, TOL, TOPK, engine, err, sequence, system_of, table_of,
    want,
)


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(MCFG, engine(), num_pages=24)


@pytest.fixture(scope="module")
def step(runner):
    """One decode step of given tokens through both pools, as
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


def _series(name, key):
    return telemetry.REGISTRY.collect().get(name, {}).get("series", {}).get(
        key, 0.0)


def _path(path):
    return _series("sutro_sparse_attention_dispatches_total", path)


# -- the shape of the thing ------------------------------------------------------

def test_the_preset_selects_and_its_widths_are_unlike_each_other():
    assert MCFG.mixers == ("mla",) * 4
    assert MCFG.ffns == ("dense", "moe", "moe", "moe")
    widths = [MCFG.q_lora_rank, MCFG.kv_lora_rank, MCFG.qk_nope_head_dim,
              MCFG.qk_rope_head_dim, MCFG.v_head_dim, MCFG.latent_width,
              MCFG.index_head_dim, MCFG.index_n_heads * MCFG.index_head_dim]
    assert len(set(widths)) == len(widths)       # nothing passes by chance
    assert MCFG.v_head_dim != MCFG.qk_nope_head_dim
    assert (MCFG.index_n_heads, MCFG.index_head_dim, TOPK) == (3, 24, 8)
    assert MCFG.pool_row_widths == (128, 24)
    for name, layers, dense, held, vocab in (
        ("glm-5", 78, 3, 256, 154_880), ("glm-5-l5-ep16", 5, 1, 16, 19_360),
    ):
        cfg = MODEL_CONFIGS[name]
        assert cfg.mixers == ("mla",) * layers and cfg.latent_width == 576
        assert cfg.ffns == ("dense",) * dense + ("moe",) * (layers - dense)
        assert cfg.pool_row_widths == (640, 128)  # whole tiles of 128 lanes
        assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
            32, 128, 2048)
        assert (cfg.qk_nope_head_dim, cfg.v_head_dim) == (192, 256)
        assert cfg.experts_held == held and cfg.moe_experts == 256
        assert cfg.vocab_size == vocab and cfg.norm_eps == 1e-5
        assert transformer.layer_groups(cfg) == [
            (0, 1, dense), (dense, 1, layers - dense)]
    # a model of latent layers with no indexer keeps what it kept
    assert MODEL_CONFIGS["tiny-joyai"].pool_row_widths == (128,)
    assert MODEL_CONFIGS["tiny-dense"].pool_row_widths == (64, 64)


def test_the_stack_holds_the_indexer_beside_the_latent_projections(runner):
    mla = runner.params["layers"]["mla"]
    assert set(mla) == {
        "attn_norm", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb",
        "wo", "w_iqb", "w_ik", "ik_norm", "ik_bias", "w_iw",
    }
    assert mla["w_iqb"].shape == (4, 28, 3 * 24)
    assert mla["w_ik"].shape == (4, 96, 24)
    assert mla["ik_norm"].shape == mla["ik_bias"].shape == (4, 24)
    assert mla["w_iw"].shape == (4, 96, 3)
    assert mla["w_kvb"].shape == (4, 36, 4 * 32)
    assert np.abs(np.asarray(mla["ik_bias"])).max() > 0   # a bias that shows


# -- (a) prefill, then single steps ------------------------------------------------

@pytest.mark.parametrize("n_prefill", [TOPK - 1, TOPK, 19])
def test_prefill_then_decode_steps_as_the_benchmark_takes_them(
    runner, n_prefill
):
    """A context under, at and over ``index_topk`` at the prefill, and
    decode steps that cross it (``n_prefill`` 7: positions 7..14)."""
    ids = np.stack([sequence(s, n_prefill + 8) for s in (4, 5)])
    got = system_of(runner).logits_through_cache(ids, n_prefill, 8)
    for g, seq in zip(got, ids):
        ref = want(runner.params, seq, range(n_prefill - 1, n_prefill + 8))
        assert err(g, ref) < TOL


def test_rows_of_different_lengths_in_one_padded_batch(runner):
    telemetry.set_enabled(True)
    s0, d0 = _path("selected"), _path("dense_short")
    rows = [sequence(1, 9), sequence(2, 27), sequence(3, 5)]
    tables = np.stack([table_of(1, 2, 3, 4), table_of(5, 6, 7, 8), table_of(9)])
    got = runner.prefill_batch(rows, tables)
    for g, row in zip(got, rows):
        assert err(g, want(runner.params, row, [len(row) - 1])[0]) < TOL
    # a bucket of 32 > index_topk: the selection bites (the smallest
    # prefill bucket, 16, is over this preset's 8 too)
    assert (_path("selected") - s0, _path("dense_short") - d0) == (1, 0)
    # two fused steps of rows of 9, 5 and 3 tokens..: the longest passes
    # index_topk; of rows of 5 and 3 alone no step does: the dense path
    tables4 = np.concatenate([tables, np.zeros((1, MP), np.int32)])
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    for past, path in (([9, 0, 5, 0], "selected"), ([3, 0, 5, 0], "dense_short")):
        before = _path(path)
        runner.decode_multi(
            np.ones((4,), np.int32), np.array(past, np.int32), tables4,
            jax.random.PRNGKey(0), zeros, ones, 2,
        )
        assert _path(path) - before == 1


def test_the_selection_is_not_everything_here(runner):
    """The check's teeth: the reference attending densely, or to the
    rows of LOWEST index score, is another model."""
    seq = sequence(40, 31)
    got = system_of(runner).logits_through_cache(seq, 23, 8)
    at = range(22, 31)
    assert err(got, want(runner.params, seq, at)) < TOL
    dense = dict(KEYS, index_topk=1 << 20)
    assert err(got, want(runner.params, seq, at, keys=dense)) > 50 * TOL
    assert err(got, want(runner.params, seq, at, select="lowest")) > 50 * TOL


def test_what_a_token_leaves_in_both_pools(runner):
    """After a prefill the latent pool's rows are the reference's
    ``(c_kv, k_pe)`` and the index pool's rows its ``k_I`` of layer 0 at
    each position (the layer whose input is the embedding)."""
    from perfbench.reference import dsa_moe, mla_moe
    from perfbench.reference.qwen3_dense import _rms, layer_weight

    seq = sequence(21, 13)
    runner.prefill(seq, table_of(5, 6))
    d = dsa_moe.dims_of(KEYS)
    w = layer_weight(runner.params["layers"]["mla"], 0)
    h = runner.params["embed"][seq].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = _rms(h, w("attn_norm"), d["eps"])
        c_kv, k_pe = mla_moe.latents(d, w, u, jnp.arange(13))
        k_i = dsa_moe.index_keys(d, w, u, jnp.arange(13))
    rows = np.asarray(runner.cache.k_pages[0, [5, 6]]).reshape(16, 128)[:13]
    assert np.abs(rows[:, :36] - np.asarray(c_kv)).max() < 1e-5
    assert np.abs(rows[:, 36:44] - np.asarray(k_pe)).max() < 1e-5
    assert not rows[:, 44:].any()
    keys = np.asarray(runner.cache.ik_pages[0, [5, 6]]).reshape(16, 24)[:13]
    assert np.abs(keys - np.asarray(k_i)).max() < 1e-5


# -- (b) chunks over a past ---------------------------------------------------------

def test_chunked_prefill_selects_among_the_earlier_chunks_rows(runner, step):
    telemetry.set_enabled(True)
    m0 = lowering.sparse_attention_counts()["masked"]
    seq = sequence(7, 59)     # prefill chunks of 24
    table = table_of(*range(1, 9))
    got = runner.prefill(seq[:57], table)
    wanted = want(runner.params, seq, [56, 57, 58])
    assert err(got, wanted[0]) < TOL
    assert err(step([seq[57]], [57], table)[0], wanted[1]) < TOL
    assert err(step([seq[58]], [58], table)[0], wanted[2]) < TOL
    assert lowering.sparse_attention_counts()["masked"] >= m0


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


# -- (c) fused and speculative windows ------------------------------------------------

@pytest.mark.parametrize("lens", [(13, 21), (3, 5)], ids=["over", "crossing"])
def test_a_fused_window_is_its_steps_one_at_a_time(runner, step, lens):
    """The window's pending index keys are among a later step's
    candidates; ``crossing``: both rows start under ``index_topk`` and
    pass it inside the window (the dense branch, then the selected)."""
    prompts = [sequence(8, lens[0]), sequence(9, lens[1])]
    tables = np.stack([table_of(1, 2, 3, 4, 5), table_of(6, 7, 8, 9, 10)])
    tables4 = np.concatenate([tables, np.zeros((2, MP), np.int32)])
    first = np.argmax(runner.prefill_batch(prompts, tables), axis=-1)
    past = np.array([lens[0], lens[1], 0, 0], np.int32)
    last = np.array([first[0], first[1], 0, 0], np.int32)
    zeros, ones = np.zeros((4,), np.float32), np.ones((4,), np.float32)
    toks, logps = runner.decode_multi(
        last, past, tables4, jax.random.PRNGKey(0), zeros, ones, 6
    )                                                   # greedy, 6 steps
    for b, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, [first[b]], toks[:, b]])
        n = len(prompt)
        ref = jax.nn.log_softmax(
            want(runner.params, seq, range(n, n + 6)), axis=-1)
        chosen = np.asarray(ref)[np.arange(6), toks[:, b]]
        assert np.max(np.abs(chosen - logps[:, b])) < 5e-4
        # the window's rows AND index keys were committed
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
    got = step([7], [n], table)[0]
    assert err(got, want(runner.params, seq, [n])[0]) < TOL


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
        ref = jax.nn.log_softmax(
            want(runner.params, seqs[b], range(n, n + 5)), -1)
        assert np.max(np.abs(np.max(np.asarray(ref), -1) - plain_lp[b])) < 5e-4
    runner.commit_verified(np.array(accepted + [0, 0], np.int32))
    for b in (0, 1):
        n = starts[b] + accepted[b]
        seq = np.concatenate([seqs[b][:n], [9, 0]])
        got = step([9], [n], tables[b])[0]
        assert err(got, want(runner.params, seq, [n])[0]) < TOL


# -- under ``use_pallas``: which body a selecting chunk takes ----------------------------

def _counts():
    return lowering.snapshot(), lowering.sparse_attention_counts()


@pytest.mark.parametrize("seeds,n_prefill", [((51, 52), 131), ((53,), 300)])
def test_a_prompt_past_index_topk_takes_the_flash_body_under_its_mask(
    interpreted, seeds, n_prefill
):
    """Two prompts of 131 tokens (the bucket of 256) and one of 300 (the
    bucket of 512), each ONE block of the flash body and 32 or 64 times
    ``index_topk`` (a count is a trace, so the cases differ in shape),
    then single steps over both pools: the reference's logits,
    ``flash_prefill`` interpreted and never ``reference``, ``masked``
    still counted."""
    runner = ModelRunner(
        MCFG, engine(use_pallas=True, max_pages_per_seq=40, max_model_len=320,
                     prefill_chunk=512, decode_multi_step=4),
        num_pages=90,
    )
    assert runner.use_pallas
    (before, sparse) = _counts()
    ids = np.stack([sequence(s, n_prefill + 4) for s in seeds])
    got = system_of(runner).logits_through_cache(ids, n_prefill, 4)
    for g, seq in zip(got, ids):
        ref = want(runner.params, seq, range(n_prefill - 1, n_prefill + 4))
        assert err(g, ref) < TOL
    now, sparse_now = _counts()
    assert now["flash_prefill"]["interpreted"] > before["flash_prefill"]["interpreted"]
    assert now["flash_prefill"]["reference"] == before["flash_prefill"]["reference"]
    assert now["flash_prefill"]["lowered"] == before["flash_prefill"]["lowered"]
    assert sparse_now["masked"] > sparse["masked"]


def test_steps_past_index_topk_take_the_paged_kernel_under_their_selection(
    interpreted
):
    """Pages of 64 (the kernel's gate takes a selection over pages of
    half a lane tile or more), through the scheduler: a prefill, fused
    windows and single steps of rows of 25-60 tokens under an
    ``index_topk`` of 8. Greedy tokens are the reference's; the
    selecting call is counted ``paged_decode`` interpreted under
    ``select=keep`` and never ``reference``, ``gathered`` as ever; and
    the rows FETCHED are the rows of the pages walked, over the rows
    selected."""
    runner = ModelRunner(
        MCFG, engine(use_pallas=True, kv_page_size=64, max_pages_per_seq=2,
                     prefill_chunk=64, decode_multi_step=4),
        num_pages=9,
    )
    assert runner.use_pallas
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than the others are",
               "a second, of middling length", "and a third one"]
    telemetry.set_enabled(True)
    (before, sparse) = _counts()
    forms = lowering.paged_decode_forms()
    rows0 = {k: _series("sutro_sparse_attention_rows_total", k)
             for k in ("context", "selected", "fetched")}
    out = {}
    ContinuousBatcher(runner, stop_ids=[]).run(
        [GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                    max_new_tokens=9, temperature=0.0)
         for i, p in enumerate(prompts)],
        on_result=lambda r: out.__setitem__(r.row_id, r),
    )
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, out[i].token_ids]).astype(np.int32)
        ref = want(runner.params, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(out[i].token_ids)
    now, sparse_now = _counts()
    assert now["paged_decode"]["interpreted"] > before["paged_decode"]["interpreted"]
    assert now["paged_decode"]["reference"] == before["paged_decode"]["reference"]
    key = f"paged_decode@{MCFG.num_heads} select=keep"
    assert lowering.paged_decode_forms()[key] > forms.get(key, 0)
    assert sparse_now["gathered"] > sparse["gathered"]
    rows = {k: _series("sutro_sparse_attention_rows_total", k) - v
            for k, v in rows0.items()}
    # every row-step walks ONE page of 64 rows beside its pending tokens
    # and itself, and attends to index_topk of them
    assert rows["selected"] < rows["context"] < rows["fetched"]
    assert rows["fetched"] > 64 * rows["selected"] / TOPK


def _mixer_operands(T, seed=60):
    params = transformer.init_params(MCFG, jax.random.PRNGKey(1), jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T, MCFG.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (2, T))
    return lp, x, pos


@pytest.mark.parametrize("T", [40, 200, 272])
def test_a_chunk_no_block_divides_counts_reference_and_is_the_xla_body(
    interpreted, T
):
    """Under, between and over the flash body's blocks (256 divides none
    of them): the XLA body's numbers bit for bit, ``flash_prefill``
    ``reference`` once a trace, ``masked`` counted as ever."""
    lp, x, pos = _mixer_operands(T)
    valid = jnp.asarray([T, T - 9])
    plain, _, _ = transformer.mla_mixer(MCFG, lp, x, positions=pos, valid_len=valid)
    (before, sparse) = _counts()
    told, _, _ = transformer.mla_mixer(
        MCFG, lp, x, positions=pos, valid_len=valid, use_pallas=True)
    now, sparse_now = _counts()
    np.testing.assert_array_equal(np.asarray(told), np.asarray(plain))
    assert now["flash_prefill"]["reference"] == before["flash_prefill"]["reference"] + 1
    assert now["flash_prefill"]["interpreted"] == before["flash_prefill"]["interpreted"]
    assert sparse_now["masked"] == sparse["masked"] + 1
    assert now["paged_decode"] == before["paged_decode"]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_a_chunk_over_a_paged_past_is_the_absorbed_xla_body_and_counts_nothing(
    interpreted, monkeypatch, use_pallas
):
    """``T > 1`` with pages: today's body, no kernel name touched either
    way; the flash helpers are never asked."""
    def never(*a, **k):
        raise AssertionError("the flash body was asked for a chunk over a past")

    monkeypatch.setattr(sparse_attention, "latent_flash_block", never)
    monkeypatch.setattr(sparse_attention, "latent_flash", never)
    T, past = 6, 14
    lp, x, pos = _mixer_operands(past + T)
    valid = jnp.asarray([past + T, past + T])
    _, rows, keys = transformer.mla_mixer(MCFG, lp, x, positions=pos, valid_len=valid)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    PS = 8
    pool = jnp.zeros((1, 8 * PS, MCFG.page_width), jnp.float32)
    ipool = jnp.zeros((1, 8 * PS, MCFG.index_head_dim), jnp.float32)
    for b in range(2):
        at = table[b, jnp.arange(past) // PS] * PS + jnp.arange(past) % PS
        pool = pool.at[0, at].set(rows[b, :past])
        ipool = ipool.at[0, at].set(keys[b, :past])
    (before, sparse) = _counts()
    out, _, _ = transformer.mla_mixer(
        MCFG, lp, x[:, past:], positions=pos[:, past:],
        valid_len=jnp.asarray([T, T - 2]), pages=pool.reshape(1, 8, PS, -1),
        layer=jnp.int32(0), page_table=table,
        past_len=jnp.asarray([past, past]),
        index_pages=ipool.reshape(1, 8, PS, -1), use_pallas=use_pallas,
    )
    now, sparse_now = _counts()
    assert now == before
    assert sparse_now["masked"] == sparse["masked"] + 1
    # the whole chunk with no past, expanded: the same tokens' outputs
    whole, _, _ = transformer.mla_mixer(MCFG, lp, x, positions=pos, valid_len=valid)
    assert np.abs(np.asarray(out[0] - whole[0, past:])).max() < 2e-5
    assert np.abs(np.asarray(out[1, :T - 2] - whole[1, past:-2])).max() < 2e-5


# -- through the scheduler: tokens, spans, counters ------------------------------------

def test_through_the_scheduler_greedy_tokens_are_the_references(runner):
    tok = ByteTokenizer(vocab_size=MCFG.vocab_size)
    prompts = ["the first prompt, a little longer than a page",
               "a second", "and a third one of middling length"]
    telemetry.set_enabled(True)
    s0 = _path("selected")
    c0 = _series("sutro_sparse_attention_rows_total", "context")
    k0 = _series("sutro_sparse_attention_rows_total", "selected")
    b = ContinuousBatcher(runner, stop_ids=[])
    out = {}
    reqs = [
        GenRequest(row_id=i, prompt_ids=np.array(tok.encode(p), np.int32),
                   max_new_tokens=10, temperature=0.0)
        for i, p in enumerate(prompts)
    ]
    b.run(reqs, on_result=lambda r: out.__setitem__(r.row_id, r))
    for i, p in enumerate(prompts):
        ids = np.array(tok.encode(p), np.int32)
        seq = np.concatenate([ids, out[i].token_ids]).astype(np.int32)
        ref = want(runner.params, seq, range(len(ids) - 1, len(seq) - 1))
        assert list(np.argmax(ref, -1)) == list(out[i].token_ids)
    assert _path("selected") > s0
    context = _series("sutro_sparse_attention_rows_total", "context") - c0
    chosen = _series("sutro_sparse_attention_rows_total", "selected") - k0
    # a row-step reads at most index_topk rows of contexts of 10-55
    assert 0 < chosen < 0.6 * context
    attrs = b._tel_attrs["decode_window"]
    assert attrs["kv_rows_selected"] == TOPK < attrs["kv_rows_context"]
    info = runner.device_info()
    assert (info["index_layers"], info["index_key_width"],
            info["index_topk"]) == (4, 24, TOPK)


# -- the share through the whole model ----------------------------------------------------

@pytest.mark.parametrize("rank", [0, 3])
def test_a_share_through_the_whole_model_is_the_references_share(rank):
    """The system told its rank's share against the reference given the
    same share of the SAME uncut weights; another rank's experts
    disagree."""
    cfg = dataclasses.replace(MCFG, name="tiny-glm-dsa: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    keys = dict(KEYS, n_routed_experts=cfg.moe_experts)
    quarter = cfg.moe_experts // 4
    first = rank * quarter
    seq = sequence(30, 21)
    moe_l = dict(params["layers"]["moe"])
    for name in ("we_gate", "we_up", "we_down"):
        moe_l[name] = moe_l[name][:, first : first + quarter]
    cut = dict(params, layers=dict(params["layers"], moe=moe_l))
    share_cfg = dataclasses.replace(
        cfg, name=f"tiny-glm-dsa: rank {rank}", moe_experts_held=quarter,
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
