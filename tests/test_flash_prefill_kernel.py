"""The flash prefill body of a GQA call (``ops/pallas_flash.py``),
interpreted, against ``chunk_attention``'s XLA body: the tile
``gqa_tiles`` chooses, the walk a call takes (a static window's blocks,
the causal half, a dynamic window's) and what ``valid_len`` skips. Every
case goes through ``chunk_attention(use_pallas=True)``, so the dispatch
that decides the walk is under test too, and the count of forms
(``lowering.flash_prefill_counts``) says which one a case took."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops import lowering, pallas_flash
from sutro_tpu.ops.attention import chunk_attention

F32 = jnp.float32


def _case(seed, B, T, NH, KVH, dtype=F32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, NH, 128)), dtype)
    k = jnp.asarray(rng.standard_normal((B, T, KVH, 128)), dtype)
    v = jnp.asarray(rng.standard_normal((B, T, KVH, 128)), dtype)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    return q, k, v, pos


CASES = {
    # name: (T, NH, KVH, valid, keywords of the call, the walk it takes)
    # -- a K/V head's group, 4 to MAX_GROUP, the causal walk
    "group-4": (1024, 4, 1, [1024, 700], {}, "causal"),
    "group-6": (1024, 6, 1, [1024, 700], {}, "causal"),
    "group-8": (1024, 16, 2, [1024, 700], {}, "causal"),
    "group-9": (1024, 9, 1, [1024, 700], {}, "causal"),
    "group-16": (512, 16, 1, [512, 300], {}, "causal"),
    # -- a static window: rows past several windows, whole and padded
    "window-512-at-2048": (2048, 2, 1, [2048, 1300], dict(live_window=512), "window"),
    "window-1024-at-2048": (2048, 2, 1, [2048, 1025], dict(live_window=1024), "window"),
    "window-512-at-4096": (4096, 2, 1, [4096, 2600], dict(live_window=512), "window"),
    "window-1024-at-4096": (4096, 2, 1, [3000, 4096], dict(live_window=1024), "window"),
    # a window off the tile's grid, and one the chunk never reaches
    "window-200-at-1024": (1024, 4, 2, [1024, 520], dict(live_window=200), "window"),
    "window-over-the-chunk": (512, 4, 2, [512, 100], dict(live_window=1024), "causal"),
    # a constant the caller holds (the full layers' 0 beside a window
    # kind; a test's own scalar) bounds the walk like a kind's window
    "window-a-constant": (1024, 4, 2, [1024, 900], dict(window=jnp.int32(300)), "window"),
    "no-window-a-constant": (1024, 4, 2, [1024, 900], dict(window=jnp.int32(0)), "causal"),
    # -- valid_len: inside a tile, at a tile's edge, 0 and T
    "valid-len": (1024, 4, 2, [700, 512, 0, 1024, 1, 513], {}, "causal"),
    "valid-len-under-a-window": (
        2048, 4, 2, [700, 512, 0, 2048, 1537], dict(live_window=512), "window",
    ),
    # -- causal by blocks of 4 (padding starts at a block's edge)
    "block-length-4": (1024, 4, 2, [1024, 516, 4], dict(block_length=4), "causal"),
    # -- a sink, under each walk
    "sink": (1024, 4, 2, [1024, 600], dict(sink=True), "causal"),
    "sink-under-a-window": (
        1024, 4, 2, [1024, 600], dict(sink=True, live_window=256), "window",
    ),
    # -- the window a runtime scalar (a homogeneous scan's layer)
    "dynamic-window": (1024, 4, 2, [1024, 600], dict(traced_window=200), "dynamic"),
    "dynamic-no-window": (1024, 8, 2, [1024, 600], dict(traced_window=0), "dynamic"),
    "dynamic-window-and-sink": (
        512, 4, 2, [512, 77], dict(traced_window=5, sink=True), "dynamic",
    ),
    # -- the operands as they are: bfloat16 in, the probabilities
    # rounded to bfloat16 for their product
    "bfloat16": (1024, 8, 2, [1024, 700], dict(dtype=jnp.bfloat16), "causal"),
    "bfloat16-under-a-window": (
        1024, 8, 2, [1024, 700], dict(dtype=jnp.bfloat16, live_window=256), "window",
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_gqa_call_is_the_xla_body_over_the_tiles_it_needs(name, interpreted):
    T, NH, KVH, valid, kw, walk = CASES[name]
    kw = dict(kw)
    dtype = kw.pop("dtype", F32)
    B = len(valid)
    q, k, v, pos = _case(len(name) + T, B, T, NH, KVH, dtype)
    vl = jnp.asarray(valid, jnp.int32)
    if kw.pop("sink", False):
        kw["sink"] = jnp.asarray(
            np.random.default_rng(3).standard_normal(NH), F32
        )
    traced = kw.pop("traced_window", None)

    def call(use_pallas, window=None):
        more = dict(kw)
        # a kind's window reaches the XLA body as its ``window`` alone
        # (``live_window`` there is a window POOL's, which a call with
        # no past lacks)
        W = more.pop("live_window", 0)
        window = more.pop("window", jnp.int32(W) if W else window)
        return chunk_attention(
            q, k, v, positions=pos, valid_len=vl, use_pallas=use_pallas,
            window=window, live_window=W if use_pallas else 0, **more,
        )

    before = lowering.flash_prefill_counts()
    if traced is None:
        got, ref = call(True), call(False)
    else:
        got = jax.jit(functools.partial(call, True))(jnp.int32(traced))
        ref = call(False, jnp.int32(traced))
    grew = {
        key for key, n in lowering.flash_prefill_counts().items()
        if n > before.get(key, 0)
    }
    side = pallas_flash.gqa_tiles(
        T, NH // KVH, 128, 128, io_bytes=jnp.dtype(dtype).itemsize,
        block_length=kw.get("block_length", 1),
    )[0]
    assert grew == {
        f"flash_prefill@{NH} tile={side}x{side} walk={walk} "
        f"operands={jnp.dtype(dtype).name}"
    }
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    tol = dict(atol=2e-5, rtol=2e-5) if dtype == F32 else dict(atol=2e-2, rtol=2e-2)
    for b, n in enumerate(valid):
        np.testing.assert_allclose(got[b, :n], ref[b, :n], **tol)
        # a query tile wholly behind the row's end: exactly zero
        pad = -(-n // side) * side
        assert not got[b, pad:].any(), (name, b)
        assert np.isfinite(got[b]).all()


def test_the_tile_follows_the_shapes_and_fits():
    # the largest side that divides the chunk ...
    assert pallas_flash.gqa_tiles(8192, 9, 128, 128) == (512, 512)
    assert pallas_flash.gqa_tiles(768, 4, 128, 128) == (256, 256)
    assert pallas_flash.gqa_tiles(640, 4, 128, 128) == (128, 128)
    # ... that a block of the mask divides ...
    assert pallas_flash.gqa_tiles(1024, 4, 128, 128, block_length=4) == (512, 512)
    # ... and whose step fits: float32 operands at MAX_GROUP do not at 512
    G = pallas_flash.MAX_GROUP
    assert pallas_flash.gqa_tiles(2048, G, 128, 128, io_bytes=2) == (512, 512)
    assert pallas_flash.gqa_tiles(2048, G, 128, 128, io_bytes=4) == (256, 256)
    for side in pallas_flash.GQA_BLOCKS:
        took = pallas_flash.gqa_vmem_bytes(G, side, side, 128, 128, 2)
        assert (took <= pallas_flash.GQA_VMEM_BUDGET) == (side <= 512)
    assert pallas_flash.GQA_VMEM_BUDGET < pallas_flash.GQA_VMEM_BYTES


def test_a_window_walk_visits_the_windows_blocks_and_no_more():
    """The grid a static window gives: ``[1, 8192]`` under a window of
    512 at tiles of 512 is two key blocks a query block, where the
    causal half is sixteen."""
    def grid(**kw):
        q = jax.ShapeDtypeStruct((1, 8192, 72, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
        vl = jax.ShapeDtypeStruct((1,), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, vl: pallas_flash.flash_prefill(
                q, k, v, valid_len=vl, interpret=True, **kw)
        )(q, k, k, vl)

        def find(jp):
            for e in jp.eqns:
                if e.primitive.name == "pallas_call":
                    return e.params["grid_mapping"].grid
                for sub in jax.core.jaxprs_in_params(e.params):
                    got = find(sub)
                    if got:
                        return got
        return tuple(find(jaxpr.jaxpr))

    assert grid(live_window=512) == (1, 8, 16, 2)
    assert grid(live_window=1024) == (1, 8, 16, 3)
    assert grid() == (1, 8, 16, 16)
    # the smallest tile (the builder's keyword): 320 of 2,080 steps a head
    assert grid(live_window=512, tiles=(128, 128)) == (1, 8, 64, 5)
