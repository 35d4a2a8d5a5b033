"""The BLOCK form of the paged decode kernel (``ops/pallas_paged.
paged_block_attention``, interpret mode on the CPU) against
``chunk_attention``'s XLA form under the block mask: a block's ``Bk``
queries a row all see the row's pages, the window's earlier blocks and
all ``Bk`` current keys. Blocks of 4 and 8, contexts that end on and off
a page's edge, a row with no past, with and without a window of earlier
blocks; and which path ``chunk_attention`` itself takes."""

import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops import lowering
from sutro_tpu.ops.attention import chunk_attention
from sutro_tpu.ops.pallas_paged import paged_block_attention

L, LAYER = 3, 1
NH, KVH, Dh, PS, MP = 4, 2, 16, 8, 5
# a dead slot (no past), one whole page, off a page's edge, several
# pages ending on an edge, a full table less the block
PAST = {4: [0, 8, 12, 24, 36, 4, 16, 28], 8: [0, 8, 16, 24, 32, 8, 16, 24]}


def _case(Bk: int, blocks_before: int, seed: int = 57):
    rng = np.random.default_rng([seed, Bk, blocks_before])
    past = PAST[Bk]
    B, KD = len(past), KVH * Dh
    f32 = jnp.float32
    q = jnp.asarray(rng.standard_normal((B, Bk, NH, Dh)), f32)
    k = jnp.asarray(rng.standard_normal((B, Bk, KVH, Dh)), f32)
    v = jnp.asarray(rng.standard_normal((B, Bk, KVH, Dh)), f32)
    NP = 1 + B * MP
    kp = jnp.asarray(rng.standard_normal((L, NP, PS, KD)), f32)
    vp = jnp.asarray(rng.standard_normal((L, NP, PS, KD)), f32)
    table = jnp.asarray(
        1 + rng.permutation(B * MP).reshape(B, MP).astype(np.int32)
    )
    past_len = jnp.asarray(past, jnp.int32)
    W = (blocks_before + 1) * Bk
    win = {}
    if blocks_before:
        win = dict(
            win_k=jnp.asarray(rng.standard_normal((B, W, KD)), f32),
            win_v=jnp.asarray(rng.standard_normal((B, W, KD)), f32),
            win_len=jnp.asarray(blocks_before * Bk, jnp.int32),
        )
    start = past_len + blocks_before * Bk
    positions = start[:, None] + jnp.arange(Bk, dtype=jnp.int32)[None]
    return q, k, v, kp, vp, table, past_len, positions, win


@pytest.mark.parametrize("blocks_before", [0, 2])
@pytest.mark.parametrize("Bk", [4, 8])
@pytest.mark.parametrize("rows", [1, 4])
def test_block_form_matches_the_xla_form(Bk, blocks_before, rows):
    q, k, v, kp, vp, table, past_len, positions, win = _case(Bk, blocks_before)
    got = paged_block_attention(
        q, kp, vp, jnp.asarray(LAYER, jnp.int32), table, past_len, k, v,
        interpret=True, rows=rows, **win,
    )
    want = chunk_attention(
        q, k, v, positions=positions,
        valid_len=jnp.full((q.shape[0],), Bk, jnp.int32),
        past_k_pages=kp, past_v_pages=vp, layer=jnp.asarray(LAYER, jnp.int32),
        page_table=table, past_len=past_len, block_length=Bk, **win,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_a_block_is_not_the_causal_chunk():
    """The control: the same block under the causal mask differs (a
    query of the block's first position sees its later keys)."""
    q, k, v, kp, vp, table, past_len, positions, win = _case(4, 0)
    common = dict(
        positions=positions, valid_len=jnp.full((q.shape[0],), 4, jnp.int32),
        past_k_pages=kp, past_v_pages=vp, layer=jnp.asarray(LAYER, jnp.int32),
        page_table=table, past_len=past_len,
    )
    block = chunk_attention(q, k, v, block_length=4, **common)
    causal = chunk_attention(q, k, v, **common)
    assert float(jnp.max(jnp.abs(block[:, 0] - causal[:, 0]))) > 1e-2
    # the block's LAST position sees the same keys under both masks
    np.testing.assert_allclose(np.asarray(block[:, -1]),
                               np.asarray(causal[:, -1]), rtol=1e-5, atol=1e-5)


def test_chunk_attention_counts_the_block_under_paged_decode(monkeypatch):
    """With the kernels on and heads the kernel takes, one block over a
    paged past is counted as ``paged_decode`` LOWERED (interpreted
    here), not as the gather's ``reference``; a chunk of another length
    still gathers."""
    from sutro_tpu.ops import pallas_paged

    rng = np.random.default_rng(3)
    B, Bk, KVH_, Dh_ = 2, 4, 1, 128
    f32 = jnp.float32
    q = jnp.asarray(rng.standard_normal((B, Bk, 2, Dh_)), f32)
    k = jnp.asarray(rng.standard_normal((B, Bk, KVH_, Dh_)), f32)
    kp = jnp.asarray(rng.standard_normal((1, 5, 8, KVH_ * Dh_)), f32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    past_len = jnp.asarray([8, 12], jnp.int32)
    real = pallas_paged.paged_decode_attention
    monkeypatch.setattr(
        pallas_paged, "paged_decode_attention",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}),
    )

    def call(T):
        return chunk_attention(
            q[:, :T], k[:, :T], k[:, :T],
            positions=past_len[:, None] + jnp.arange(T)[None],
            valid_len=jnp.full((B,), T, jnp.int32),
            past_k_pages=kp, past_v_pages=kp, layer=jnp.asarray(0, jnp.int32),
            page_table=table, past_len=past_len, block_length=Bk,
            use_pallas=True,
        )

    before = lowering.snapshot()["paged_decode"]
    kernel = call(Bk)
    mid = lowering.snapshot()["paged_decode"]
    assert mid["interpreted"] == before["interpreted"] + 1
    assert mid["reference"] == before["reference"]
    call(2)
    assert lowering.snapshot()["paged_decode"]["reference"] == mid["reference"] + 1
    plain = chunk_attention(
        q, k, k, positions=past_len[:, None] + jnp.arange(Bk)[None],
        valid_len=jnp.full((B,), Bk, jnp.int32),
        past_k_pages=kp, past_v_pages=kp, layer=jnp.asarray(0, jnp.int32),
        page_table=table, past_len=past_len, block_length=Bk,
    )
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain),
                               rtol=2e-5, atol=2e-5)
