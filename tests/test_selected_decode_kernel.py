"""A selecting decode step through the paged kernel's latent variant
(``ops/pallas_paged.paged_decode_attention(keep=, keep_tail=)``),
interpreted on the CPU, against the XLA body of
``ops/sparse_attention.selected_decode`` (``lax.top_k``'s positions and a
row gather) on the same operands: the SAME set attended, the same
numbers; which body a call took, as the counters say it; that a call
WITHOUT a selection traces the program it traced before this operand
existed; and the kernel under a selection compiled for a described v5e
at the served widths, a block of 1, 2, 4 and 8 rows a grid step.

Pages of 64 throughout: the kernel's gate takes a selection over pages
of whole or half lane tiles only (the tiny models' pages of 8 count
``reference`` and keep the XLA body: the last test).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops import lowering, pallas_paged
from sutro_tpu.ops import sparse_attention as sa
from sutro_tpu.ops.sparse_attention import Indexer

F32 = jnp.float32
NH, WIDTH, VALUES, LAYERS, DI, NHI, PS = 4, 128, 40, 2, 16, 2, 64
SCALE = 0.2


def _operands(past, MP, topk, slots, win_len, *, seed, ties=False,
              scattered=True, low=()):
    """A batch's operands. ``ties``: index queries and keys of small
    whole numbers, so that many candidates score the same. ``low``: the
    candidates given an index key no head scores above zero, ``"own"``
    and / or a pending slot's number."""
    rng = np.random.default_rng(seed)
    B = len(past)
    NP = 1 + B * MP
    draw = (
        (lambda *s: np.round(rng.standard_normal(s))) if ties
        else (lambda *s: rng.standard_normal(s))
    )
    pool = jnp.asarray(rng.standard_normal((LAYERS, NP, PS, WIDTH)), F32)
    ipool = jnp.asarray(draw(LAYERS, NP, PS, DI), F32)
    table = 1 + np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    if scattered:
        table = 1 + rng.permutation(B * MP).astype(np.int32).reshape(B, MP)
    iq = draw(B, 1, NHI, DI)
    own_k, win_k = draw(B, 1, DI), draw(B, max(slots, 1), DI)
    # against every head's query at once: no product above zero
    away = -iq.sum(axis=2) * 100.0 - 1.0
    if ties:
        away = np.zeros_like(away)      # relu(0) = 0 ties with zeros
    for which in low:
        if which == "own":
            own_k = away
        else:
            win_k[:, which] = away[:, 0]
    index = Indexer(
        q=jnp.asarray(iq, F32),
        w=jnp.asarray(np.abs(draw(B, 1, NHI)) + 1.0, F32),
        k=jnp.asarray(own_k, F32), topk=topk, pages=ipool,
        win=jnp.asarray(win_k[:, :slots], F32) if slots else None,
    )
    past_len = jnp.asarray(past, jnp.int32)
    win = {}
    if slots:
        win = dict(
            win_rows=jnp.asarray(rng.standard_normal((B, slots, WIDTH)), F32),
            win_len=jnp.asarray(win_len, jnp.int32),
        )
    q = jnp.asarray(rng.standard_normal((B, 1, NH, WIDTH)), F32)
    row = jnp.asarray(rng.standard_normal((B, 1, WIDTH)), F32)
    kw = dict(
        positions=(past_len + (win_len if slots else 0))[:, None],
        scale=SCALE, pages=pool, layer=jnp.int32(1),
        page_table=jnp.asarray(table), past_len=past_len,
        value_width=VALUES, **win,
    )
    return q, row, index, kw


#: name: (past, table pages, index_topk, window slots, pending, more).
#: The batch gives the rows a grid step (``rows_per_step``: 8, 2, and 1
#: for a batch of 3)
CASES = {
    "lengths-differ-one-row-a-step": (
        [70, 200, 131], 4, 32, 0, 0, {}),
    "two-rows-a-step-a-full-window": (
        [250, 90], 4, 32, 4, 4, {}),
    "eight-rows-a-step-short-rows-and-a-padding-row-among-them": (
        [65, 100, 128, 129, 191, 256, 33, 0], 4, 48, 4, 3, {}),
    "a-row-at-exactly-index-topk-plus-one": (
        [64, 61], 2, 64, 0, 0, {}),
    "exactly-index-topk-plus-one-with-pending": (
        [60, 3], 2, 64, 4, 4, {}),
    "ties-at-the-kth-score": (
        [200, 150, 256], 4, 40, 4, 2, dict(ties=True)),
    "ties-where-the-own-row-scores-zero": (
        [120, 70], 2, 24, 4, 3, dict(ties=True, low=("own", 1))),
    "own-row-and-a-pending-token-not-chosen": (
        [230, 140], 4, 32, 4, 3, dict(low=("own", 1))),
    "win-len-under-the-windows-width": (
        [100, 180], 4, 32, 8, 1, {}),
    "an-ascending-table": (
        [256, 190], 4, 56, 4, 2, dict(scattered=False)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_under_a_selection_is_the_xla_body(
    interpreted, monkeypatch, name
):
    past, MP, topk, slots, win_len, more = CASES[name]
    q, row, index, kw = _operands(
        past, MP, topk, slots, win_len, seed=len(name), **more)
    want, (chosen_pos, chosen) = sa.selected_decode(
        q, row, index, return_selection=True, **kw)

    seen = {}
    inner = pallas_paged.paged_decode_attention

    def spy(*a, **k):
        seen.update(k)
        return inner(*a, **k)

    monkeypatch.setattr(pallas_paged, "paged_decode_attention", spy)
    got = sa.selected_decode(q, row, index, use_pallas=True, **kw)
    assert got.shape == want.shape == (len(past), 1, NH, VALUES)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    # the SAME set: the mask's kept candidates are top_k's positions
    # (paged position p at p; a pending slot at past + slot; the own row
    # at past + pending)
    B, CTX = len(past), MP * PS
    keep = np.concatenate(
        [np.asarray(seen["keep"]), np.asarray(seen["keep_tail"])], axis=1)
    assert keep.shape == (B, CTX + slots + 1)
    pending = win_len if slots else 0
    for b in range(B):
        at = np.flatnonzero(keep[b])
        pos = np.where(at < CTX, at, past[b] + at - CTX)
        pos[at == CTX + slots] = past[b] + pending
        assert sorted(pos) == sorted(
            np.asarray(chosen_pos[b])[np.asarray(chosen[b])])
        assert len(at) == min(topk, past[b] + pending + 1)
        own = past[b] + pending
        if "own" in more.get("low", ()) and past[b] + pending + 1 > topk:
            assert own not in pos and past[b] + 1 not in pos
        if name.startswith("a-row-at-exactly"):
            assert len(at) == past[b] + 1 - (past[b] + 1 > topk)


def test_a_selecting_call_is_told_from_the_dense_branchs(interpreted):
    """One program, both branches (``sparse_latent_attention``'s
    ``lax.cond``): ``gathered`` counted as ever, ``paged_decode``
    interpreted twice, ONE of the two under ``select=keep``."""
    # a shape of its own: a count is a trace, and a jitted kernel's
    # second call at a shape is none
    q, row, index, kw = _operands([200, 90, 17], 5, 32, 5, 2, seed=5)
    kw.update(valid_len=jnp.ones((3,), jnp.int32))
    before = lowering.snapshot()["paged_decode"]
    forms = lowering.paged_decode_forms().get("paged_decode@4 select=keep", 0)
    sparse = lowering.sparse_attention_counts()
    got = jax.jit(lambda q, row: sa.sparse_latent_attention(
        q, row, None, index, use_pallas=True, **kw))(q, row)
    want = sa.selected_decode(q, row, index, **{
        k: v for k, v in kw.items() if k != "valid_len"})
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    now = lowering.snapshot()["paged_decode"]
    assert now["interpreted"] == before["interpreted"] + 2
    assert now["reference"] == before["reference"]
    assert lowering.paged_decode_forms() == {
        "paged_decode@4 select=keep": forms + 1}
    assert lowering.sparse_attention_counts()["gathered"] == sparse["gathered"] + 2
    assert not any(
        k.startswith("paged_decode") for k in lowering.flash_prefill_counts())


def test_pages_of_eight_keep_the_xla_body_and_count_reference(interpreted):
    """The gate refuses a selection over pages no half lane tile wide:
    ``lax.top_k`` and the gather, bit for bit, ``reference`` with the
    gate's name beside the heads."""
    rng = np.random.default_rng(9)
    B, MP, ps = 2, 6, 8
    pool = jnp.asarray(rng.standard_normal((LAYERS, 1 + B * MP, ps, WIDTH)), F32)
    ipool = jnp.asarray(rng.standard_normal((LAYERS, 1 + B * MP, ps, DI)), F32)
    index = Indexer(
        q=jnp.asarray(rng.standard_normal((B, 1, NHI, DI)), F32),
        w=jnp.ones((B, 1, NHI), F32),
        k=jnp.asarray(rng.standard_normal((B, 1, DI)), F32), topk=8,
        pages=ipool,
    )
    past = jnp.asarray([40, 21], jnp.int32)
    kw = dict(
        positions=past[:, None], scale=SCALE, pages=pool, layer=jnp.int32(0),
        page_table=jnp.asarray(
            1 + rng.permutation(B * MP).reshape(B, MP), jnp.int32),
        past_len=past, value_width=VALUES,
    )
    q = jnp.asarray(rng.standard_normal((B, 1, NH, WIDTH)), F32)
    row = jnp.asarray(rng.standard_normal((B, 1, WIDTH)), F32)
    assert pallas_paged.paged_decode_supported(q[:, 0], pool)
    assert not pallas_paged.paged_decode_supported(
        q[:, 0], pool, selection_pages=MP)
    before = lowering.snapshot()["paged_decode"]
    plain = sa.selected_decode(q, row, index, **kw)
    told = sa.selected_decode(q, row, index, use_pallas=True, **kw)
    np.testing.assert_array_equal(np.asarray(told), np.asarray(plain))
    now = lowering.snapshot()["paged_decode"]
    assert now["reference"] == before["reference"] + 1
    assert now["interpreted"] == before["interpreted"]
    assert "a selection over pages of 8" in (
        lowering.kernel_heads_counts()["paged_decode@4"]["gate"])


def test_the_selection_is_counted_among_a_blocks_bytes():
    """GLM-5's call (64 heads, rows of 640, pages of 64, a ring of 24, a
    window of 15): 8 rows a step with the selection's blocks counted,
    which are one int32 slab of the block's rows and two tiles a row,
    two buffers each."""
    shape = (64, 640, 640, 64, 24, 8, 15)
    modes = dict(pool_bytes=2, io_bytes=2, shared=True)
    lanes = pallas_paged._keep_lanes(64, 256)
    assert lanes == 256 * 64
    assert pallas_paged._keep_lanes(64, 3) == 256      # whole lane tiles
    assert pallas_paged._keep_lanes(8, 6) == 256       # and a tile more
    dense = pallas_paged.decode_vmem_bytes(8, *shape, **modes)
    under = pallas_paged.decode_vmem_bytes(8, *shape, keep_lanes=lanes, **modes)
    assert under - dense == 2 * (8 * lanes * 4 + 2 * 8 * 4096)
    assert under <= pallas_paged.VMEM_LIMIT_BYTES
    assert pallas_paged.rows_per_step(16, *shape, keep_lanes=lanes, **modes) == 8
    # a table four times as long no longer fits eight rows beside the ring
    assert pallas_paged.rows_per_step(
        16, *shape, keep_lanes=4 * lanes, **modes) == 4


# -- a call without a selection is the program it was ------------------------------

#: name: (B, heads, head width, KV heads (None: the latent variant), pool
#: pages, page size, table pages, window slots, more; sha256 of the
#: call's jaxpr text at commit a66aee6, the parent of PR 63, first 16
#: digits): the 4B cell's call, JoyAI's, Xing's, GLM-5's dense branch, a
#: window pool's (``window_start``) and one under a shared prefix's carry
UNSELECTED = {
    "4b": (64, 32, 128, 8, 579, 64, 16, 8, {}, "09bd13449e0ddb9f"),
    "joyai": (32, 32, 640, None, 1025, 64, 128, 8,
              {"scale": 192 ** -0.5}, "42db48fc1dfafc14"),
    "xing": (32, 32, 640, None, 1025, 64, 64, 8,
             {"scale": 0.1}, "1ceff65596f2ccfd"),
    "glm5-dense": (16, 64, 640, None, 4097, 64, 256, 15,
                   {"scale": 1 / 16}, "f7f04581cd0d3ac1"),
    "mellum2-window-pool": (32, 32, 128, 8, 600, 64, 40, 8,
                            {"window_start": True}, "7c2bad877e420476"),
    "classify-prefix": (64, 32, 128, 8, 579, 64, 16, 0,
                        {"prefix": True}, "5eec14f6baf925a9"),
}


def _call_shapes(B, NH, Dh, KVH, NP, ps, MP, W, prefix=False):
    bf, S = jnp.bfloat16, jax.ShapeDtypeStruct
    latent = KVH is None
    KD = Dh if latent else KVH * Dh
    pool = S((5, NP, ps, KD), bf)
    args = dict(
        q=S((B, NH, Dh), bf), k_pages=pool, v_pages=None if latent else pool,
        layer=S((), jnp.int32), page_table=S((B, MP), jnp.int32),
        past_len=S((B,), jnp.int32),
        k_cur=S((B, 1, Dh), bf) if latent else S((B, KVH, Dh), bf),
        v_cur=None if latent else S((B, KVH, Dh), bf),
        window=S((), jnp.int32),
    )
    if W:
        args.update(win_k=S((B, W, KD), bf), win_len=S((), jnp.int32))
        if not latent:
            args["win_v"] = S((B, W, KD), bf)
    if prefix:
        args.update(
            pfx_cnt=S((B,), jnp.int32), m0=S((B, NH), jnp.float32),
            l0=S((B, NH), jnp.float32), acc0=S((B, NH, KD), jnp.float32),
        )
    return args


@pytest.mark.parametrize("name", sorted(UNSELECTED))
def test_a_call_without_a_selection_traces_the_program_it_had(name):
    *shape, more, parent = UNSELECTED[name]
    more = dict(more)
    args = _call_shapes(*shape, prefix=more.pop("prefix", False))
    dynamic = {k: v for k, v in args.items() if v is not None}

    def call(given):
        return pallas_paged.paged_decode_attention(
            **{**args, **given}, **more)

    forms = lowering.paged_decode_forms()
    text = str(jax.make_jaxpr(call)(dynamic))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == parent
    assert lowering.paged_decode_forms() == forms


# -- compiled for a described v5e at GLM-5's widths --------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1),
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def silent_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without a chip: off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows", [None, 4, 2, 1])
def test_the_selecting_call_compiles_for_a_v5e_at_glm5s_widths(
    one_chip, silent_cache, rows
):
    """16 rows of 64 heads over tables of 256 pages of 64 rows 640 wide,
    a window of 15 slots: Mosaic takes the selection's slab (a block's
    rows a sublane each, the row picked by a compare), the slices at
    whole lane tiles, and the blocks fit the scoped VMEM at the rows a
    step the call picks (8)."""
    B, NH, Dh, MP, W = 16, 64, 640, 256, 15
    args = _call_shapes(B, NH, Dh, None, 4097, PS, MP, W)
    args.update(
        keep=jax.ShapeDtypeStruct((B, MP * PS), jnp.bool_),
        keep_tail=jax.ShapeDtypeStruct((B, W + 1), jnp.bool_),
    )
    dynamic = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
        for k, v in args.items() if v is not None
    }
    taken = lowering.paged_decode_rows_per_step().get(rows or 8, 0)

    def call(given):
        return pallas_paged.paged_decode_attention(
            **{**args, **given}, scale=1 / 16, rows=rows)

    compiled = jax.jit(call).trace(dynamic).lower(
        lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert lowering.paged_decode_rows_per_step()[rows or 8] == taken + 1
    assert pallas_paged.paged_decode_supported(
        args["q"], args["k_pages"], selection_pages=MP)
