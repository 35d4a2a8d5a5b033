"""The pieces of the latent-attention model one at a time, against the
plain reference (``perfbench/reference/mla_moe.py``) or against each
other: the rotary pairing, the two forms of one mixer on the same latent
rows, the blocked attention against the whole square, and the shares of
a routed layer added up. float32 on the CPU; each tolerance says why.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import mla_moe
from perfbench.reference.qwen3_dense import _rms, layer_weight
from sutro_tpu.models import transformer
from sutro_tpu.ops import attention, moe
from tests.joyai_common import KEYS, MCFG, sequence


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(MCFG, jax.random.PRNGKey(3), jnp.float32)


# -- the rotary pairing ---------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 7, 8), (2, 7, 4, 8), (1, 5, 64)])
def test_the_systems_rotation_is_the_interleaved_one_and_not_the_half_split(shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    pos = jnp.asarray(
        np.random.default_rng(1).integers(0, 4000, shape[:2]), jnp.int32)
    got = transformer.apply_rope_interleaved(x, pos, 32_000_000.0)
    for b in range(shape[0]):
        inter = mla_moe.rope_interleaved(x[b], pos[b], 32_000_000.0)
        half = mla_moe.rope_half_split(x[b], pos[b], 32_000_000.0)
        # a signed permutation and two products: rounding only
        assert np.abs(np.asarray(got[b] - inter)).max() < 1e-5
        # the other pairing is another function (they must DIFFER)
        assert np.abs(np.asarray(inter - half)).max() > 0.1


def test_a_rotation_keeps_each_pairs_length_and_position_zero_is_the_identity():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 8), jnp.float32)
    pos = jnp.asarray([[0, 5, 900]], jnp.int32)
    y = np.asarray(transformer.apply_rope_interleaved(x, pos, 1e4))
    xs = np.asarray(x)
    assert np.abs(y[0, 0] - xs[0, 0]).max() < 1e-6
    pair = lambda a: (a.reshape(-1, 4, 2) ** 2).sum(-1)   # noqa: E731
    assert np.abs(pair(y[0]) - pair(xs[0])).max() < 1e-5


# -- two forms of one mixer -------------------------------------------------------

def _mixer_inputs(params, seed, T):
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T, MCFG.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (2, T))
    return lp, x, pos


@pytest.mark.parametrize("split", [1, 8, 13, 19])
def test_absorbed_over_pages_is_expanded_over_the_chunk(params, split):
    """The same 20 tokens: all at once with no past (EXPANDED), and the
    last ``20 - split`` over a pool that holds the first ``split``'s
    rows (ABSORBED, T > 1 or T = 1 over a paged past). The same numbers
    up to the order of two products."""
    T, PS = 20, 8
    lp, x, pos = _mixer_inputs(params, 4, T)
    valid = jnp.asarray([T, T], jnp.int32)
    whole, rows, _ = transformer.mla_mixer(
        MCFG, lp, x, positions=pos, valid_len=valid)
    # a pool of 3 layers whose layer 2 holds the rows, two pages a row
    pool = jnp.zeros((3, 8, PS, MCFG.page_width), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    flat = pool.reshape(3, 8 * PS, -1)
    for b in range(2):
        at = (table[b, jnp.arange(T) // PS] * PS + jnp.arange(T) % PS)
        flat = flat.at[2, at[:split]].set(rows[b, :split])
    pool = flat.reshape(pool.shape)
    n = T - split
    tail, tail_rows, _ = transformer.mla_mixer(
        MCFG, lp, x[:, split:], positions=pos[:, split:],
        valid_len=jnp.asarray([n, n], jnp.int32), pages=pool,
        layer=jnp.int32(2), page_table=table,
        past_len=jnp.asarray([split, split], jnp.int32),
    )
    scale = float(np.abs(np.asarray(whole)).max())
    assert np.abs(np.asarray(tail - whole[:, split:])).max() < 1e-5 * scale
    assert np.abs(np.asarray(tail_rows - rows[:, split:])).max() < 1e-6


def test_the_mixer_is_the_references_attention(params):
    lp, x, pos = _mixer_inputs(params, 5, 17)
    got, rows, _ = transformer.mla_mixer(
        MCFG, lp, x, positions=pos, valid_len=jnp.asarray([17, 17]))
    d = mla_moe.dims_of(KEYS)
    w = layer_weight(params["layers"]["mla"], 1)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            ref = mla_moe.attention(d, w, x[b], pos[b])
            c_kv, k_pe = mla_moe.latents(d, w, x[b], pos[b])
            scale = float(np.abs(np.asarray(ref)).max())
            assert np.abs(np.asarray(got[b] - ref)).max() < 1e-5 * scale
            assert np.abs(np.asarray(rows[b, :, :40] - c_kv)).max() < 1e-5
            assert np.abs(np.asarray(rows[b, :, 40:48] - k_pe)).max() < 1e-5
            assert rows.shape[-1] == 128 and not np.asarray(rows[b, :, 48:]).any()


@pytest.mark.parametrize("block", [4, 7, 32])
def test_blocks_of_queries_are_the_whole_square(block):
    """``latent_attention`` a block of queries at a time (keys up to the
    block's end) against one block that holds every query."""
    q = jax.random.normal(jax.random.PRNGKey(6), (2, 19, 3, 24))
    k = jax.random.normal(jax.random.PRNGKey(7), (2, 19, 3, 24))
    v = jax.random.normal(jax.random.PRNGKey(8), (2, 19, 3, 20))
    pos = jnp.broadcast_to(jnp.arange(19, dtype=jnp.int32)[None], (2, 19))
    kw = dict(positions=pos, valid_len=jnp.asarray([19, 11]), scale=0.2)
    whole = attention.latent_attention(q, k, v, block_q=64, **kw)
    got = attention.latent_attention(q, k, v, block_q=block, **kw)
    assert got.shape == (2, 19, 3, 20)
    assert np.abs(np.asarray(got - whole))[0].max() < 1e-5
    assert np.abs(np.asarray(got - whole))[1, :11].max() < 1e-5


# -- the shares add up --------------------------------------------------------------

def _uncut():
    cfg = dataclasses.replace(MCFG, name="tiny-joyai: uncut",
                              moe_experts_held=0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    return cfg, params, dict(KEYS, n_routed_experts=cfg.moe_experts)


@pytest.mark.parametrize("method", ["ragged", "dense"])
def test_the_four_ranks_shares_add_up_to_the_uncut_layer(method):
    """What ranks 0-3 (experts 0-3, 4-7, 8-11, 12-15) compute, with the
    shared expert counted ONCE, is what the uncut reference gives for
    the whole layer; each assignment lands on exactly one rank."""
    cfg, params, keys = _uncut()
    d = mla_moe.dims_of(keys)
    moe_l = params["layers"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 5, cfg.hidden_size))
    flat, index = x.reshape(15, -1), 2
    with jax.default_matmul_precision("highest"):
        whole, _ = mla_moe.routed_ffn(d, moe_l, index, flat, shared=True)
        only_shared = mla_moe.swiglu(
            flat, moe_l["shared_gate"][index], moe_l["shared_up"][index],
            moe_l["shared_down"][index],
        )
    quarter = cfg.moe_experts // 4
    total = np.zeros((3, 5, cfg.hidden_size), np.float32)
    counted = 0
    for first in range(0, cfg.moe_experts, quarter):
        share_cfg = dataclasses.replace(
            cfg, moe_experts_held=quarter, moe_first_expert=first
        )
        lp = {k: v[index] for k, v in moe_l.items()}
        held = {n: lp[n][first : first + quarter]
                for n in ("we_gate", "we_up", "we_down")}
        y, counts = moe.moe_mlp(
            x, lp["router"], held["we_gate"], held["we_up"], held["we_down"],
            top_k=cfg.moe_top_k, activation=cfg.activation, method=method,
            first_expert=first, route=transformer._router_form(share_cfg, lp),
            return_counts=True,
        )
        total += np.asarray(y)
        counted += int(counts[first : first + quarter].sum())
        assert int(counts.sum()) == 15 * cfg.moe_top_k   # over the router's E
    assert counted == 15 * cfg.moe_top_k     # each assignment on ONE rank
    total += np.asarray(only_shared).reshape(total.shape)
    scale = float(np.abs(np.asarray(whole)).max())
    assert np.abs(total.reshape(15, -1) - np.asarray(whole)).max() < 1e-5 * scale


@pytest.mark.parametrize("crowded", [False, True], ids=["even", "crowded"])
def test_a_small_share_of_many_rows_runs_its_own_rows_alone(crowded):
    """256 tokens x top-4 = 1,024 expanded rows of which a chip that
    holds 4 of 16 experts owns about 256: the grouped products take the
    first 512 sorted rows (``_share_row_cap``), or every row where the
    router sent this chip over twice its even share; the sums are the
    dense form's either way. A share of a half, or a decode step's few
    rows, keeps the program it had."""
    assert moe._share_row_cap(1024, 4, 16) == 512
    assert moe._share_row_cap(32_768, 16, 256) == 4096     # a 4,096-token prefill
    assert moe._share_row_cap(2048, 16, 256) == 512        # the numbers check's
    assert moe._share_row_cap(256, 16, 256) is None        # a decode step
    assert moe._share_row_cap(1024, 8, 16) is None         # a share of a half
    H, F, E, held, K = 32, 24, 16, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (1, 256, H), jnp.float32)
    router = jax.random.normal(ks[1], (H, E), jnp.float32)
    wg, wu = (jax.random.normal(k, (held, H, F), jnp.float32) / 6 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (held, F, H), jnp.float32) / 5
    # crowded: every token's top-4 are the four held experts
    rb = jnp.where(jnp.arange(E) < held, 50.0 if crowded else 0.0, 0.0)
    call = functools.partial(
        moe.moe_mlp, x, router, wg, wu, wd, top_k=K, first_expert=0,
        router_b=rb, return_counts=True,
    )
    got, counts = call(method="ragged")
    want, _ = call(method="dense")
    owned = int(np.asarray(counts)[:held].sum())
    assert owned == 1024 if crowded else 128 < owned <= 512
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * scale
    assert "cond" in str(jax.make_jaxpr(lambda: call(method="ragged"))())
    half = functools.partial(
        moe.moe_mlp, x, router[:, :8], wg, wu, wd, top_k=K, method="ragged")
    assert "cond" not in str(jax.make_jaxpr(half)())


def test_the_references_shares_add_up_and_a_wrong_rank_disagrees():
    cfg, params, keys = _uncut()
    h = np.asarray(
        jax.random.normal(jax.random.PRNGKey(9), (11, cfg.hidden_size)))
    whole = np.asarray(mla_moe.ffn_of_layer(keys, params, 2, h))
    parts = [
        np.asarray(mla_moe.ffn_of_layer(
            keys, params, 2, h, experts=(first, 4), shared=False))
        for first in (0, 4, 8, 12)
    ]
    # rank 0 with the shared expert, counted once
    with_shared = np.asarray(
        mla_moe.ffn_of_layer(keys, params, 2, h, experts=(0, 4)))
    scale = np.abs(whole).max()
    assert np.abs(with_shared + sum(parts[1:]) - whole).max() < 1e-5 * scale
    # a rank's part is no other rank's
    assert np.abs(parts[0] - parts[1]).max() > 1e-2 * scale
    # the dense layer has no share: the same whatever is held
    dense = np.asarray(mla_moe.ffn_of_layer(keys, params, 0, h))
    cut = np.asarray(mla_moe.ffn_of_layer(keys, params, 0, h, experts=(4, 4)))
    assert np.array_equal(dense, cut)


def test_the_reference_refuses_what_it_does_not_follow():
    for key, value in (
        ("rope_scaling", {"type": "yarn", "factor": 40}),
        ("rope_interleave", False), ("scoring_func", "softmax"),
        ("topk_method", "greedy"), ("n_group", 8), ("norm_topk_prob", False),
        ("q_lora_rank", None), ("tie_word_embeddings", True),
    ):
        with pytest.raises(NotImplementedError):
            mla_moe.dims_of(dict(KEYS, **{key: value}))


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(mla_moe))
    names = [
        (n.module or "") if isinstance(n, ast.ImportFrom)
        else ",".join(a.name for a in n.names)
        for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
    ]
    assert not [n for n in names if "sutro_tpu" in n]
    assert mla_moe.ROUTED is True and mla_moe.TIE_MARGIN == 0.02
