"""The three kernels on a latent layer's path, interpreted on the CPU,
each against the XLA form it replaces (``ops/attention.latent_attention``,
``kvcache._scatter_rows``): the paged decode kernel's latent variant (ONE
pool whose rows serve both products, every head to the one stored row),
the flash kernel's body at zero-padded heads under the layer's own scale
(and under an indexer's selection as a mask tile, against the XLA body of
``ops/sparse_attention.masked_attention`` and a gather of the chosen
keys), and the in-place write of one row a token into one pool. One page, one
head and one row at a time where a sum would hide a slip; then the whole
tiny model through them (prefill, single steps, a fused window) against
the float32 reference, and what the counts say.

The compiled (Mosaic) lowering of the same calls at the served widths is
held by tests/perfbench/test_aot_joyai_v5e.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.engine import kvcache
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.ops import attention, lowering, pallas_flash, pallas_kv, pallas_paged
from sutro_tpu.ops.attention import latent_attention
from sutro_tpu.ops.sparse_attention import Indexer, masked_attention

from tests.joyai_common import (
    MCFG, TOL, engine, err, sequence, system_of, want,
)

F32 = jnp.float32
NH, W, VW, PS, MP, NP, L = 4, 128, 40, 8, 6, 20, 3
SCALE = 24 ** -0.5


def _pool_and_tables(rng, B):
    pool = jnp.asarray(rng.standard_normal((L, NP, PS, W)), F32)
    table = np.zeros((B, MP), np.int32)
    for b in range(B):
        table[b] = 1 + b * MP + rng.permutation(MP)    # scattered pages
    return pool, jnp.asarray(table)


# -- (a) one decode step over the latent pages --------------------------------------

@pytest.mark.parametrize("past", [
    [0, 0, 0],          # nothing cached: the current row alone
    [8, 1, 7],          # exactly one page, one token, a page less one
    [9, 16, 17],        # a page boundary crossed
    [48, 33, 5],        # the whole table, five pages, one
])
@pytest.mark.parametrize("window_slots", [0, 4])
def test_the_paged_kernels_latent_variant_is_the_absorbed_xla_form(
    past, window_slots
):
    rng = np.random.default_rng(sum(past) + window_slots)
    B = len(past)
    pool, table = _pool_and_tables(rng, B)
    q = jnp.asarray(rng.standard_normal((B, 1, NH, W)), F32)
    row = jnp.asarray(rng.standard_normal((B, 1, W)), F32)
    past_len = jnp.asarray(past, jnp.int32)
    win = {}
    if window_slots:
        # two of the window's four slots hold rows; the query is two past
        win = dict(
            win_rows=jnp.asarray(
                rng.standard_normal((B, window_slots, W)), F32),
            win_len=jnp.asarray(2, jnp.int32),
        )
    pos = (past_len + (2 if window_slots else 0))[:, None]
    ref = latent_attention(
        q, row, None, positions=pos, valid_len=jnp.ones((B,), jnp.int32),
        scale=SCALE, pages=pool, layer=jnp.int32(1), page_table=table,
        past_len=past_len, value_width=VW, **win,
    )
    got = pallas_paged.paged_decode_attention(
        q[:, 0], pool, None, jnp.int32(1), table, past_len, row, None,
        jnp.int32(0), scale=SCALE, interpret=True,
        **({"win_k": win["win_rows"], "win_len": win["win_len"]} if win else {}),
    )
    assert got.shape == (B, NH, W)
    np.testing.assert_allclose(
        np.asarray(got[..., :VW]), np.asarray(ref[:, 0]), rtol=2e-5, atol=2e-5
    )


def test_each_head_reads_the_one_row_and_no_other_heads_query():
    """One head's query changed: that head's output alone moves."""
    rng = np.random.default_rng(3)
    pool, table = _pool_and_tables(rng, 1)
    q = jnp.asarray(rng.standard_normal((1, NH, W)), F32)
    row = jnp.asarray(rng.standard_normal((1, 1, W)), F32)
    call = functools.partial(
        pallas_paged.paged_decode_attention, k_pages=pool, v_pages=None,
        layer=jnp.int32(0), page_table=table,
        past_len=jnp.asarray([19], jnp.int32), k_cur=row, v_cur=None,
        window=jnp.int32(0), scale=SCALE, interpret=True,
    )
    base = np.asarray(call(q))
    moved = np.asarray(call(q.at[0, 2].add(1.0)))
    changed = np.abs(moved - base).max(axis=-1)[0]
    assert changed[2] > 1e-3 and changed[[0, 1, 3]].max() == 0.0


# -- (b) a chunk with no past: the flash body at padded heads -----------------------

@pytest.mark.parametrize("T,block", [(128, None), (256, None), (256, 256),
                                     (512, 256)])
def test_flash_at_zero_padded_heads_is_the_expanded_xla_form(T, block):
    """Q and K padded to 128 lanes, V to a width of its own (two tiles
    against one: the body takes V's from V), the layer's scale, the
    kernel's own blocks and the latent layers' larger ones."""
    rng = np.random.default_rng(T)
    B, Dq, Dv = 2, 24 + 128, 20                 # K two lane tiles, V one
    q = jnp.asarray(rng.standard_normal((B, T, NH, Dq)), F32)
    k = jnp.asarray(rng.standard_normal((B, T, NH, Dq)), F32)
    v = jnp.asarray(rng.standard_normal((B, T, NH, Dv)), F32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    valid = jnp.asarray([T, T - 37], jnp.int32)
    scale = Dq ** -0.5
    ref = latent_attention(
        q, k, v, positions=pos, valid_len=valid, scale=scale, block_q=64)

    def padded(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, -x.shape[-1] % 128),))

    call = functools.partial(
        pallas_flash.flash_prefill, padded(q), padded(k), padded(v),
        interpret=True, block=block,
    )
    got = call(scale=scale)
    assert got.shape == (B, T, NH, 128)         # V's own padded width
    for b in range(B):       # a padded tail's outputs are never used
        n = int(valid[b])
        np.testing.assert_allclose(
            np.asarray(got[b, :n, :, :Dv]), np.asarray(ref[b, :n]),
            rtol=2e-5, atol=2e-5,
        )
    # the scale is the layer's, not 1/sqrt of the padded head
    assert np.abs(np.asarray(call() - got)).max() > 1e-3


def test_the_dispatch_pads_and_blocks_as_the_kernel_wants(monkeypatch):
    """``latent_attention(use_pallas=True)`` on an expanded chunk: the
    same numbers as its XLA form through the flash body, bfloat16
    operands as they are."""
    monkeypatch.setattr(
        pallas_flash, "flash_prefill",
        functools.partial(pallas_flash.flash_prefill, interpret=True),
    )
    rng = np.random.default_rng(9)
    B, T, Dq, Dv = 1, 256, 24, 20
    q, k = (jnp.asarray(rng.standard_normal((B, T, NH, Dq)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, T, NH, Dv)), jnp.bfloat16)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    args = dict(positions=pos, valid_len=jnp.asarray([T]), scale=SCALE)
    before = lowering.snapshot()["flash_prefill"]["interpreted"]
    got = latent_attention(q, k, v, use_pallas=True, **args)
    assert lowering.snapshot()["flash_prefill"]["interpreted"] == before + 1
    ref = latent_attention(q, k, v, **args)
    assert got.shape == ref.shape == (B, T, NH, Dv) and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=0.03, atol=0.03,
    )


# -- (b') a selecting chunk: the flash body under the selection's mask ---------------

#: tiny-glm-dsa's: 4 heads of 12 + 8 and 20, an indexer of 3 heads of 24
#: that keeps 8; a chunk of four blocks of 128
SEL = dict(T=512, Dq=20, Dv=20, NHi=3, Di=24, topk=8, block=128)


def _selecting_chunk(rng, B, tied):
    """Heads and an indexer whose scores peak at ONE earlier position a
    query (keys and queries are phases of 12 frequencies: the score of
    (t, s) is a sum of cos(w (s - c_t))), so that a query's 8 kept keys
    cluster round c_t, most key blocks of a query keep nothing, and the
    first kept key of many queries lies blocks in. ``tied``: every index
    key the same, every score equal: the 8 LOWEST positions, all in the
    first block, whatever the query's."""
    T, Di, NHi = SEL["T"], SEL["Di"], SEL["NHi"]
    q, k = (jnp.asarray(rng.standard_normal((B, T, NH, SEL["Dq"])), F32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, T, NH, SEL["Dv"])), F32)
    w = 2 * np.pi * rng.uniform(0.002, 0.03, Di // 2)

    def phases(at):                                    # [.., T] -> [.., T, Di]
        return np.concatenate(
            [np.cos(at[..., None] * w), np.sin(at[..., None] * w)], axis=-1)

    target = np.floor(rng.uniform(size=(B, T)) * (np.arange(T) + 1))
    ik = phases(np.broadcast_to(np.arange(T, dtype=np.float64), (B, T)))
    if tied:
        ik = np.ones_like(ik)
    iq = np.broadcast_to(phases(target)[:, :, None], (B, T, NHi, Di))
    index = Indexer(
        q=jnp.asarray(iq, F32),
        w=jnp.asarray(rng.uniform(0.5, 1.5, (B, T, NHi)), F32),
        k=jnp.asarray(ik, F32), topk=SEL["topk"],
    )
    return q, k, v, index


def _gathered(q, k, v, keep, scale):
    """One query at a time over the keys ``keep`` names, float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape[:2] + v.shape[2:])
    for b, t in zip(*np.nonzero(keep.any(-1))):
        at = np.nonzero(keep[b, t])[0]
        s = np.einsum("nd,xnd->nx", q[b, t], k[b, at]) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b, t] = np.einsum("nx,xnd->nd", p / p.sum(-1, keepdims=True), v[b, at])
    return out


@pytest.mark.parametrize("valid,tied", [
    ([512], False),          # every row real
    ([512], True),           # equal index scores: the lower positions
    ([301], False),          # a padded tail, its last block all padding
    ([512, 77], False),      # two rows of different lengths
    ([130, 512], True),
], ids=["whole", "tied", "padded", "two-rows", "two-rows-tied"])
def test_the_flash_body_under_a_selection_is_the_xla_body_and_a_gather(
    monkeypatch, valid, tied
):
    monkeypatch.setattr(
        pallas_flash, "flash_prefill",
        functools.partial(pallas_flash.flash_prefill, interpret=True),
    )
    monkeypatch.setattr(attention, "_LATENT_FLASH_BLOCKS", (SEL["block"],))
    T, B = SEL["T"], len(valid)
    rng = np.random.default_rng(sum(valid) + tied)
    q, k, v, index = _selecting_chunk(rng, B, tied)
    scale = SEL["Dq"] ** -0.5
    kw = dict(
        positions=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T)),
        valid_len=jnp.asarray(valid, jnp.int32), scale=scale, return_mask=True,
    )
    before = lowering.snapshot()["flash_prefill"]
    masked = lowering.sparse_attention_counts()["masked"]
    ref, ref_keep = masked_attention(q, k, v, index, **kw)
    assert lowering.snapshot()["flash_prefill"] == before    # nothing counted
    got, keep = masked_attention(q, k, v, index, use_pallas=True, **kw)
    now = lowering.snapshot()["flash_prefill"]
    # (a count is a TRACE: a second case of one shape finds the first's)
    assert now["interpreted"] >= max(before["interpreted"], 1)
    assert (now["reference"], now["lowered"]) == (
        before["reference"], before["lowered"])
    assert lowering.sparse_attention_counts()["masked"] == masked + 2
    # the tests' view of the selection is the same array either way
    keep = np.asarray(keep)
    assert keep.dtype == bool and keep.shape == (B, T, T)
    assert (keep == np.asarray(ref_keep)).all()
    assert not np.triu(keep, 1).any()                 # inside the triangle
    got = np.asarray(got)
    assert got.shape == ref.shape and np.isfinite(got).all()
    gathered = _gathered(q, k, v, keep, scale)
    for b, n in enumerate(valid):
        counts = keep[b, :n].sum(-1)
        assert (counts == np.minimum(np.arange(n) + 1, SEL["topk"])).all()
        np.testing.assert_allclose(
            got[b, :n], np.asarray(ref[b, :n]), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[b, :n], gathered[b, :n], rtol=2e-5, atol=2e-5)
    # what the kernel had to get right: of a query's key blocks at or
    # under its diagonal most keep nothing, and many a query's first kept
    # key lies blocks in (tied: the first block alone keeps anything,
    # the diagonal block of a later query nothing)
    blk = SEL["block"]
    per_block = keep.reshape(B, T, T // blk, blk).any(-1)        # [B, T, nK]
    causal = np.arange(T // blk)[None] <= (np.arange(T) // blk)[:, None]
    real = np.arange(T)[None] < np.asarray(valid)[:, None]       # [B, T]
    pairs = (causal[None] & real[..., None])[:, blk:]    # past the first block
    assert (~per_block[:, blk:] & pairs).sum() > 0.5 * pairs.sum()
    first = per_block.argmax(-1)
    if tied:
        assert (per_block[real][:, 1:] == 0).all()
    elif max(valid) == T:
        assert (first[real] >= 2).sum() > 20


def test_a_query_that_keeps_nothing_comes_out_zero_and_finite():
    """``keep`` all zeros for one block of queries and for one query
    elsewhere: zeros there, the other rows as without them."""
    rng = np.random.default_rng(11)
    T = 256
    q, k, v = (jnp.asarray(rng.standard_normal((1, T, NH, 128)), F32)
               for _ in range(3))
    keep = np.tril(rng.uniform(size=(1, T, T)) < 0.05)
    keep[0, np.arange(T), np.arange(T)] = True
    keep[0, 128:] &= np.arange(T)[None] >= 128       # a first block with nothing
    call = functools.partial(
        pallas_flash.flash_prefill, q, k, v, interpret=True)
    want = np.asarray(call(keep=jnp.asarray(keep, jnp.int8)))
    np.testing.assert_allclose(
        want, _gathered(q, k, v, keep, 128 ** -0.5), rtol=2e-5, atol=2e-5)
    keep[0, :128] = False
    keep[0, 200] = False
    got = np.asarray(call(keep=jnp.asarray(keep, jnp.int8)))
    assert np.isfinite(got).all()
    assert not got[0, :128].any() and not got[0, 200].any()
    rest = np.r_[128:200, 201:T]
    np.testing.assert_array_equal(got[0, rest], want[0, rest])


@pytest.mark.parametrize("block", [None, 256])
def test_flash_with_no_selection_is_the_program_it_was(block):
    """``keep=None`` traces the call without the argument: the same
    operands, the same numbers bit for bit; a selection is ONE more."""
    rng = np.random.default_rng(12)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, NH, 128)), F32)
               for _ in range(3))
    call = functools.partial(
        pallas_flash.flash_prefill, interpret=True, block=block)

    def kernel_inputs(fn, *args):
        def walk(jaxpr):
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call":
                    yield len(e.invars)
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from walk(sub)
        return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))

    plain = lambda q, k, v: call(q, k, v)                       # noqa: E731
    none = lambda q, k, v: call(q, k, v, keep=None)             # noqa: E731
    kept = lambda q, k, v, m: call(q, k, v, keep=m)             # noqa: E731
    assert str(jax.make_jaxpr(plain)(q, k, v)) == str(jax.make_jaxpr(none)(q, k, v))
    assert kernel_inputs(plain, q, k, v) == kernel_inputs(none, q, k, v) == [5]
    mask = jnp.asarray(np.tril(np.ones((1, 256, 256), np.int8)))
    assert kernel_inputs(kept, q, k, v, mask) == [6]
    np.testing.assert_array_equal(
        np.asarray(plain(q, k, v)), np.asarray(none(q, k, v)))
    # the whole triangle kept: the same numbers as no selection
    np.testing.assert_allclose(
        np.asarray(kept(q, k, v, mask)), np.asarray(plain(q, k, v)),
        rtol=1e-6, atol=1e-6)


# -- (c) one row a token into one pool ----------------------------------------------

@pytest.mark.parametrize("starts,valids,tb", [
    ([0, 8, 3], [16, 16, 5], 16),     # aligned, offset, ragged
    ([7, 41, 0], [16, 7, 0], 16),     # page-crossing, the table's end, empty
    ([0, 5, 47], [40, 33, 1], 40),    # runs of several pages
    ([6, 0, 13], [1, 1, 1], 1),       # a decode step's one row
])
def test_the_one_pool_write_lands_what_the_scatter_lands(starts, valids, tb):
    rng = np.random.default_rng(tb)
    B = 3
    pool, table = _pool_and_tables(rng, B)
    rows = jnp.asarray(rng.standard_normal((L, B, tb, W)), F32)
    start, valid = jnp.asarray(starts, jnp.int32), jnp.asarray(valids, jnp.int32)
    flat = kvcache._flat_slots(table, start, valid, tb, PS)
    ref = kvcache._scatter_rows(pool, flat, rows)
    got = pallas_kv.row_write_pallas(
        pool.copy(), rows, table, start, valid, interpret=True)
    # page 0 is the garbage page: the scatter dumps padding there, the
    # kernel skips it
    np.testing.assert_array_equal(np.asarray(got)[:, 1:], np.asarray(ref)[:, 1:])
    untouched = np.ones((NP,), bool)
    untouched[np.asarray(table).ravel()] = False
    untouched[0] = False
    np.testing.assert_array_equal(
        np.asarray(got)[:, untouched], np.asarray(pool)[:, untouched])


# -- the whole model through the three ----------------------------------------------

def test_the_model_through_the_three_kernels_is_the_reference(interpreted):
    runner = ModelRunner(
        MCFG, engine(use_pallas=True, max_pages_per_seq=24, max_model_len=192,
                     prefill_chunk=160, decode_multi_step=4),
        num_pages=60,
    )
    assert runner.use_pallas
    before = lowering.snapshot()
    # the benchmark's own door: a prefill of 131 tokens (bucket 256: the
    # flash body), then 6 single steps over the latent pages, each
    # written in place
    ids = np.stack([sequence(s, 137) for s in (31, 32)])
    got = system_of(runner).logits_through_cache(ids, 131, 6)
    for g, seq in zip(got, ids):
        assert err(g, want(runner.params, seq, range(130, 137))) < TOL
    now = lowering.snapshot()
    for kernel in ("flash_prefill", "paged_decode", "kv_write"):
        assert now[kernel]["interpreted"] > before[kernel]["interpreted"], kernel
        assert now[kernel]["lowered"] == before[kernel]["lowered"]
    # a fused window of 4 greedy steps: the window's rows beside the pages
    seq = sequence(33, 140)
    table = np.zeros((24,), np.int32)
    table[:19] = np.arange(30, 49)
    first = int(np.argmax(runner.prefill(seq, table)))
    tables = np.concatenate([table[None], np.zeros((3, len(table)), np.int32)])
    toks, logps = runner.decode_multi(
        np.array([first, 0, 0, 0], np.int32),
        np.array([140, 0, 0, 0], np.int32), tables, jax.random.PRNGKey(0),
        np.zeros((4,), np.float32), np.ones((4,), np.float32), 4,
    )
    full = np.concatenate([seq, [first], toks[:, 0]])
    ref = jax.nn.log_softmax(
        want(runner.params, full, range(140, 144)), axis=-1)
    chosen = np.asarray(ref)[np.arange(4), toks[:, 0]]
    assert np.max(np.abs(chosen - logps[:, 0])) < 5e-4


def test_a_shape_no_kernel_takes_counts_reference_and_runs_in_xla():
    """A chunk shorter than the flash body's block, and a chunk of
    several tokens over a paged past: the XLA forms, said so by name."""
    from sutro_tpu.models import transformer

    params = transformer.init_params(MCFG, jax.random.PRNGKey(1), F32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, MCFG.hidden_size))
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    cache = kvcache.alloc_cache(MCFG, engine(), 4, dtype=F32)
    table = jnp.asarray([[1, 2]], jnp.int32)
    before, forms = lowering.snapshot(), lowering.latent_counts()
    plain, rows, _ = transformer.mla_mixer(
        MCFG, lp, x, positions=pos, valid_len=jnp.asarray([6]))
    told, _, _ = transformer.mla_mixer(
        MCFG, lp, x, positions=pos, valid_len=jnp.asarray([6]),
        use_pallas=True,
    )                                                   # expanded, T = 6
    np.testing.assert_array_equal(np.asarray(told), np.asarray(plain))
    cache = kvcache.write_kv(
        cache, transformer.MixedChunk(k=jnp.stack([rows] * 4)), None, table,
        jnp.asarray([0]), jnp.asarray([4]),
    )
    transformer.mla_mixer(
        MCFG, lp, x[:, 4:], positions=pos[:, 4:],
        valid_len=jnp.asarray([2]), pages=cache.k_pages, layer=jnp.int32(0),
        page_table=table, past_len=jnp.asarray([4]), use_pallas=True,
    )                                                   # absorbed, T = 2
    now = lowering.snapshot()
    for kernel in ("flash_prefill", "paged_decode"):
        assert now[kernel]["reference"] == before[kernel]["reference"] + 1
        assert now[kernel]["lowered"] == before[kernel]["lowered"]
        assert now[kernel]["interpreted"] == before[kernel]["interpreted"]
    after = lowering.latent_counts()
    assert after["expanded"] == forms["expanded"] + 2
    assert after["absorbed"] == forms["absorbed"] + 1
