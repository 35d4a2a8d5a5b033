"""The pieces of learned sparse attention one at a time, against the
plain reference (``perfbench/reference/dsa_moe.py``) or against each
other: the indexer's key, the SELECTION itself (the positions the system
attends to are the reference's top-k at every query of a prompt and of
decode steps; ties to the lower position), the two forms of one mixer on
the same rows (selected-absorbed against masked-expanded), the blocked
and grouped mask against one block, and the shares of a routed layer
added up. float32 on the CPU; each tolerance says why.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import dsa_moe
from perfbench.reference.qwen3_dense import _rms, layer_weight
from sutro_tpu.models import transformer
from sutro_tpu.ops import sparse_attention as sa
from tests.glm_dsa_common import KEYS, MCFG, TOPK, sequence


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(MCFG, jax.random.PRNGKey(3), jnp.float32)


@functools.partial(jax.jit, static_argnames=("with_pages",))
def _mixer(lp, x, pos, valid, pages=None, layer=None, page_table=None,
           past_len=None, index_pages=None, with_pages=False):
    kw = dict(pages=pages, layer=layer, page_table=page_table,
              past_len=past_len, index_pages=index_pages) if with_pages else {}
    return transformer.mla_mixer(
        MCFG, lp, x, positions=pos, valid_len=valid, **kw)


@functools.partial(jax.jit, static_argnames=("topk", "selection"))
def _ref_attention(w_stack, x, pos, topk=TOPK, selection=False):
    d = dict(dsa_moe.dims_of(KEYS), index_topk=topk)
    return dsa_moe.attention(
        d, layer_weight(w_stack, 1), x, pos, return_selection=selection)


def _mixer_inputs(params, seed, T, B=2):
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, T, MCFG.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    return lp, x, pos


def _pools(rows, keys, table, upto, PS=8):
    """A latent pool and an index pool of 3 layers whose layer 2 holds
    the first ``upto`` rows and index keys of each sequence."""
    B, T = rows.shape[:2]
    pool = jnp.zeros((3, 8 * PS, MCFG.page_width), jnp.float32)
    ipool = jnp.zeros((3, 8 * PS, MCFG.index_head_dim), jnp.float32)
    for b in range(B):
        at = table[b, jnp.arange(T) // PS] * PS + jnp.arange(T) % PS
        pool = pool.at[2, at[:upto]].set(rows[b, :upto])
        ipool = ipool.at[2, at[:upto]].set(keys[b, :upto])
    return (pool.reshape(3, 8, PS, -1), ipool.reshape(3, 8, PS, -1))


# -- the indexer ------------------------------------------------------------------

def test_the_index_key_is_the_references_layer_norm_and_partial_rotation(params):
    lp, x, pos = _mixer_inputs(params, 5, 17)
    c_q = transformer.rms_norm(x @ lp["w_qa"], lp["q_norm"], MCFG.norm_eps, False)
    got = transformer._indexer(MCFG, lp, x, c_q, pos)
    d = dsa_moe.dims_of(KEYS)
    w = layer_weight(params["layers"]["mla"], 1)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            ref = dsa_moe.index_keys(d, w, x[b], pos[b])
            # a LayerNorm, a signed permutation and two products
            assert np.abs(np.asarray(got.k[b] - ref)).max() < 1e-5
    # the rotation reaches the first qk_rope_head_dim alone, the bias shows
    flat = transformer._indexer(MCFG, lp, x, c_q, jnp.zeros_like(pos))
    moved = np.abs(np.asarray(got.k - flat.k)).max(axis=(0, 1))
    assert moved[:2].min() > 0.1 and moved[8:].max() == 0
    no_bias = dict(lp, ik_bias=jnp.zeros_like(lp["ik_bias"]))
    other = transformer._indexer(MCFG, no_bias, x, c_q, pos)
    assert np.abs(np.asarray(got.k - other.k)).max() > 1e-2
    assert got.w.dtype == jnp.float32 and got.q.shape == (2, 17, 3, 24)


# -- the selection ------------------------------------------------------------------

def _reference_selection(params, x, pos):
    with jax.default_matmul_precision("highest"):
        return np.stack([
            np.asarray(_ref_attention(
                params["layers"]["mla"], x[b], pos[b], selection=True))
            for b in range(x.shape[0])
        ])


@functools.partial(jax.jit, static_argnames=("block_q",))
def _system_mask(lp, x, pos, valid, **kw):
    """The kept pairs of a chunk with no past, the mixer's own way."""
    c_q = transformer.rms_norm(x @ lp["w_qa"], lp["q_norm"], MCFG.norm_eps, False)
    index = transformer._indexer(MCFG, lp, x, c_q, pos)
    B, T = x.shape[:2]
    q = jnp.zeros((B, T, MCFG.num_heads, 20))
    return sa.masked_attention(
        q, q, q, index, positions=pos, valid_len=valid, scale=1.0,
        return_mask=True, **kw,
    )[1]


@pytest.mark.parametrize("T", [TOPK - 1, TOPK, TOPK + 1, 40])
def test_a_prompts_kept_pairs_are_the_references_top_k_at_every_query(params, T):
    lp, x, pos = _mixer_inputs(params, 6, T)
    keep = np.asarray(_system_mask(lp, x, pos, jnp.asarray([T, T])))
    ref = _reference_selection(params, x, pos)
    assert keep.shape == ref.shape == (2, T, T)
    assert (keep == ref).all()
    counts = keep.sum(-1)
    assert (counts == np.minimum(np.arange(T) + 1, TOPK)[None]).all()


@pytest.mark.parametrize("block", [4, 8])
def test_blocks_and_groups_of_queries_keep_the_same_pairs(params, block):
    """64 queries a block of 4 or 8 at a time (8 groups under
    ``lax.map``) against one block that holds every query."""
    lp, x, pos = _mixer_inputs(params, 7, 64, B=1)
    valid = jnp.asarray([64])
    whole = np.asarray(_system_mask(lp, x, pos, valid, block_q=64))
    assert (np.asarray(_system_mask(lp, x, pos, valid, block_q=block)) == whole).all()
    assert (whole == _reference_selection(params, x, pos)).all()


@pytest.mark.parametrize("split", [TOPK - 2, TOPK - 1, TOPK, 19])
def test_a_decode_steps_chosen_rows_are_the_references_top_k(params, split):
    """ONE query over a pool that holds ``split`` rows: under, at and
    over ``index_topk`` (``split`` + the step's own token), through the
    mixer's own entry; the positions ``selected_decode`` gathers are the
    reference's row of the full selection."""
    T = split + 1
    lp, x, pos = _mixer_inputs(params, 8, T)
    _, rows, keys = _mixer(lp, x, pos, jnp.asarray([T, T]))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    pool, ipool = _pools(rows, keys, table, split)
    c_q = transformer.rms_norm(
        x[:, split:] @ lp["w_qa"], lp["q_norm"], MCFG.norm_eps, False)
    index = transformer._indexer(
        MCFG, lp, x[:, split:], c_q, pos[:, split:])._replace(pages=ipool)
    q = jnp.zeros((2, 1, MCFG.num_heads, MCFG.page_width))
    _, (chosen_pos, chosen) = sa.selected_decode(
        q, rows[:, split:], index, positions=pos[:, split:], scale=1.0,
        pages=pool, layer=jnp.int32(2), page_table=table,
        past_len=jnp.asarray([split, split]), value_width=36,
        return_selection=True,
    )
    ref = _reference_selection(params, x, pos)[:, -1]        # [B, T]
    for b in range(2):
        got = np.zeros((T,), bool)
        got[np.asarray(chosen_pos[b])[np.asarray(chosen[b])]] = True
        assert (got == ref[b]).all()
        assert got.sum() == min(T, TOPK)


def test_equal_scores_go_to_the_lower_position_in_both_forms():
    """Index keys that are all the same give every position the same
    score: the mask and ``lax.top_k`` both keep the LOWEST positions,
    and the query's own position only while there is room."""
    scores = jnp.zeros((1, 3, 12)).at[0, 1, 7].set(1.0).at[0, 2, 2].set(-1.0)
    valid = jnp.ones((1, 3, 12), bool).at[0, :, 11].set(False)
    keep = np.asarray(sa.topk_mask(scores, valid, 4))
    assert list(np.flatnonzero(keep[0, 0])) == [0, 1, 2, 3]
    assert list(np.flatnonzero(keep[0, 1])) == [0, 1, 2, 7]
    assert list(np.flatnonzero(keep[0, 2])) == [0, 1, 3, 4]
    # negative zero is zero: no order between them
    signed = jnp.asarray([[0.0, -0.0, 0.0, -0.0, 1.0]])
    assert list(np.flatnonzero(np.asarray(
        sa.topk_mask(signed, jnp.ones((1, 5), bool), 3)))) == [0, 1, 4]
    # fewer valid than k: every valid one
    few = jnp.arange(12.0)[None] < 3
    assert (np.asarray(sa.topk_mask(jnp.zeros((1, 12)), few, 4)) == few).all()
    top, at = jax.lax.top_k(jnp.asarray([[0.5, 2.0, 0.5, 0.5, -jnp.inf]]), 3)
    assert list(np.asarray(at[0])) == [1, 0, 2]


def test_the_mask_is_exact_against_a_sort_on_random_scores():
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((5, 300)).astype(np.float32)
    scores[:, ::7] = scores[:, 3:4]           # many exact ties
    valid = rng.random((5, 300)) < 0.8
    keep = np.asarray(sa.topk_mask(jnp.asarray(scores), jnp.asarray(valid), 40))
    for r in range(5):
        order = sorted(np.flatnonzero(valid[r]),
                       key=lambda i: (-scores[r, i], i))[:40]
        assert sorted(order) == list(np.flatnonzero(keep[r]))


# -- two forms of one mixer ------------------------------------------------------------

@pytest.mark.parametrize("split", [1, TOPK - 1, 13, 19])
def test_selected_absorbed_over_pages_is_masked_expanded_over_the_chunk(
    params, split
):
    """The same 20 tokens: all at once with no past (EXPANDED under the
    mask), and the last ``20 - split`` over pools that hold the first
    ``split``'s rows and index keys (ABSORBED: masked for T > 1,
    gathered for the one query of ``split`` 19). The same numbers up to
    the order of two products."""
    T = 20
    lp, x, pos = _mixer_inputs(params, 4, T)
    whole, rows, keys = _mixer(lp, x, pos, jnp.asarray([T, T]))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    pool, ipool = _pools(rows, keys, table, split)
    n = T - split
    tail, tail_rows, tail_keys = _mixer(
        lp, x[:, split:], pos[:, split:], jnp.asarray([n, n], jnp.int32),
        pages=pool, layer=jnp.int32(2), page_table=table,
        past_len=jnp.asarray([split, split], jnp.int32), index_pages=ipool,
        with_pages=True,
    )
    scale = float(np.abs(np.asarray(whole)).max())
    assert np.abs(np.asarray(tail - whole[:, split:])).max() < 1e-5 * scale
    assert np.abs(np.asarray(tail_rows - rows[:, split:])).max() < 1e-6
    assert np.abs(np.asarray(tail_keys - keys[:, split:])).max() < 1e-6


def test_the_mixer_is_the_references_attention(params):
    lp, x, pos = _mixer_inputs(params, 5, 23)
    got, _, _ = _mixer(lp, x, pos, jnp.asarray([23, 23]))
    stack = params["layers"]["mla"]
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            ref = _ref_attention(stack, x[b], pos[b])
            scale = float(np.abs(np.asarray(ref)).max())
            assert np.abs(np.asarray(got[b] - ref)).max() < 1e-5 * scale
            # and NOT the dense attention of the same weights
            dense = _ref_attention(stack, x[b], pos[b], topk=1 << 20)
            assert np.abs(np.asarray(got[b] - dense)).max() > 1e-2 * scale


def test_seeded_weights_spread_the_attentions_logits(params):
    """``seeded_peaked_attention``: a row's attention logits spread about
    that many standard deviations over its keys (near 1 without), so
    that a subset's softmax is not the whole's."""
    flat = transformer.init_params(
        dataclasses.replace(MCFG, seeded_peaked_attention=0.0),
        jax.random.PRNGKey(3), jnp.float32)

    def spread(p):
        lp = jax.tree_util.tree_map(lambda a: a[1], p["layers"]["mla"])
        x = jax.random.normal(jax.random.PRNGKey(9), (1, 64, MCFG.hidden_size))
        c_q = transformer.rms_norm(x @ lp["w_qa"], lp["q_norm"], 1e-5, False)
        q = (c_q @ lp["w_qb"]).reshape(64, 4, 20)[..., :12]
        c_kv = transformer.rms_norm(
            (x @ lp["w_kva"])[..., :36], lp["kv_norm"], 1e-5, False)
        k = (c_kv @ lp["w_kvb"]).reshape(64, 4, 32)[..., :12]
        return float(jnp.std(jnp.einsum("tnd,snd->nts", q, k) / 20 ** 0.5))

    assert 1.3 * spread(flat) < spread(params) < 1.7 * spread(flat)
    # V's columns and every other draw are what they were
    a, b = params["layers"]["mla"], flat["layers"]["mla"]
    assert np.array_equal(
        np.asarray(a["w_kvb"]).reshape(4, 36, 4, 32)[..., 12:],
        np.asarray(b["w_kvb"]).reshape(4, 36, 4, 32)[..., 12:])
    assert np.array_equal(np.asarray(a["wo"]), np.asarray(b["wo"]))


# -- the shares of a routed layer ---------------------------------------------------------

def test_four_ranks_routed_parts_add_up_to_the_uncut_layer():
    """The four ranks' routed parts with the shared expert counted once
    add up to the uncut reference's layer output (attention, indexer and
    the dense layer are every rank's whole), and a wrong rank's experts
    do not."""
    cfg = dataclasses.replace(MCFG, name="tiny-glm-dsa: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    keys = dict(KEYS, n_routed_experts=cfg.moe_experts)
    h = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (11, cfg.hidden_size)), np.float32)
    whole = np.asarray(dsa_moe.ffn_of_layer(keys, params, 2, h))
    parts = [
        np.asarray(dsa_moe.ffn_of_layer(
            keys, params, 2, h, experts=(4 * r, 4), shared=False))
        for r in range(4)
    ]
    shared = np.asarray(
        dsa_moe.ffn_of_layer(keys, params, 2, h, experts=(0, 4))) - parts[0]
    scale = np.abs(whole).max()
    assert np.abs(shared).max() > 0.05 * scale
    assert np.abs(sum(parts) + shared - whole).max() < 1e-5 * scale
    wrong = sum(parts[:3]) + parts[0] + shared
    assert np.abs(wrong - whole).max() > 1e-2 * scale
    # the reference's layers leave the residual stream as the system's do
    seq = sequence(2, 15)
    hid = dsa_moe.logits_and_near_ties(
        keys, params, seq, [14], return_hidden=True)[0]
    assert hid.shape == (1, cfg.hidden_size) and np.isfinite(hid).all()


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(dsa_moe))
    names = [
        (n.module or "") if isinstance(n, ast.ImportFrom)
        else ",".join(a.name for a in n.names)
        for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
    ]
    assert not [n for n in names if "sutro_tpu" in n]
    assert dsa_moe.ROUTED is True and dsa_moe.TIE_MARGIN == 0.02
    # what it refuses rather than guesses
    for bad in (dict(indexer_rope_interleave=False),
                dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
                dict(index_head_dim=4), dict(rope_scaling={"type": "yarn"})):
        with pytest.raises(NotImplementedError):
            dsa_moe.dims_of(dict(KEYS, **bad))


def test_a_held_shares_prefill_rows_have_the_room_the_model_asks_for():
    """``ModelConfig.moe_share_rows``: the even shares of rows a held
    share's products take in a prefill (``ops/moe._share_row_cap``): 2
    for every model that does not say, 4 for this family's presets; the
    sums are the same whichever branch runs."""
    from sutro_tpu.models.configs import MODEL_CONFIGS
    from sutro_tpu.ops import moe

    assert moe._share_row_cap(65_536, 16, 256) == 8_192
    assert moe._share_row_cap(65_536, 16, 256, 2) == 8_192
    assert moe._share_row_cap(65_536, 16, 256, 4) == 16_384
    assert moe._share_row_cap(65_536, 16, 256, 16) is None   # over half
    assert moe._share_row_cap(128, 16, 256, 4) is None       # a decode step
    assert MODEL_CONFIGS["glm-5-l5-ep16"].moe_share_rows == 4
    assert MODEL_CONFIGS["joyai-llm-flash-ep16"].moe_share_rows == 2
    # a router that sends this share 5 x its even part: under 2 shares
    # every row goes through, under 8 the first rows alone: equal sums
    H, F, E, held, K = 16, 8, 32, 2, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (1, 2048, H))
    router = jax.random.normal(ks[1], (H, E)).at[:, 0].add(3.0 * jnp.ones(H))
    x = x.at[:, ::3].set(jnp.abs(x[:, ::3]))      # a third of the rows lean to 0
    we = [jax.random.normal(k, s) / 4 for k, s in zip(
        ks[2:], ((held, H, F), (held, H, F), (held, F, H)))]
    outs = [
        moe.moe_mlp(x, router, *we, top_k=K, share_rows=n) for n in (2, 8)
    ]
    assert moe._share_row_cap(4096, held, E, 2) == 512
    assert moe._share_row_cap(4096, held, E, 8) == 2048
    np.testing.assert_allclose(
        np.asarray(outs[0]), np.asarray(outs[1]), rtol=1e-5, atol=1e-5)
