"""Pallas TPU kernel: in-place KV page writes (fused-layout pool).

The XLA path for landing a chunk's K/V into the paged cache is a scatter
over a ~GB-scale buffer; under jit donation that costs several ms per
call of pure buffer churn (measured ~8 ms/donated buffer on v5e,
qwen3-0.6b, 2026-07). This kernel keeps the pool in place with
``input_output_aliases`` and explicit DMAs.

Constraint driving the design: the pool's fused layout ``[L, NP, PS,
KVH*Dh]`` (engine/kvcache.py) makes the page-slot axis a TILED memref
dim, so single-row DMA writes are illegal (8-row alignment). Instead the
kernel is a page-granular read-modify-write:

- the token run of each batch row is split IN-GRAPH into per-page
  segments (page id, row range, shift), passed as scalar prefetch;
- grid ``(segments, layer-chunks)``: each step DMAs a ``[lc, PS, KD]``
  slab of the target page (``lc`` layers at once, sized to a VMEM
  budget — fewer, bigger DMAs), rotates the row's token buffer so token
  ``j`` lands on its page row ((start+j) % PS) via ``pltpu.roll``
  (dynamic shift, f32 — Mosaic's rotate is 32-bit only), blends rows
  inside the segment's range, and DMAs the slab back;
- empty segments (rows whose run touches fewer pages than the static
  bound, padding rows) skip all work under ``pl.when``.

The RMW costs one extra page read per touched page — writes happen once
per prefill chunk / decode window, so this is noise next to the decode
loop — and buys exact in-place semantics at any offset with zero pool
copies or padding blowup.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lowering


def _kv_write_kernel(
    # scalar prefetch (flattened [B*S] segment tables)
    seg_page_ref, seg_rs_ref, seg_re_ref, seg_shift_ref, seg_row_ref,
    # then, a pool at a time (K and V; ONE for a pool of latent rows):
    # the new rows, VMEM blocks [lc, 1, Tb, KD] (layer chunk, seg row);
    # the aliased inputs, ANY [L, NP, PS, KD]; the aliased outputs; the
    # page slabs (scratch) and their semaphores
    *refs,
    page_size: int,
    layer_chunk: int,
    pools: int = 2,
):
    new_refs = refs[:pools]
    out_refs = refs[2 * pools:3 * pools]      # refs[pools:2 * pools]: unused
    pages = refs[3 * pools:4 * pools]
    sems = refs[4 * pools:5 * pools]
    s = pl.program_id(0)
    lchunk = pl.program_id(1)
    PS = page_size
    lc = layer_chunk
    page = seg_page_ref[s]
    rs = seg_rs_ref[s]
    re = seg_re_ref[s]

    @pl.when(re > rs)
    def _do():
        lsl = pl.ds(lchunk * lc, lc)
        reads = [
            pltpu.make_async_copy(out.at[lsl, page], slab, sem)
            for out, slab, sem in zip(out_refs, pages, sems)
        ]
        for dma in reads:
            dma.start()

        # token j lives at page row (start + j) % PS; rolling the token
        # buffer by -shift puts token (r + shift) at row r for every r
        shift = seg_shift_ref[s]
        Tb = new_refs[0].shape[2]
        row = jax.lax.broadcasted_iota(
            jnp.int32, (PS, new_refs[0].shape[3]), 0
        )
        sel = jnp.logical_and(row >= rs, row < re)

        def rotated(tok):  # [Tb, KD] -> [PS, KD] rolled into page rows
            t = tok.astype(jnp.float32)
            if Tb < PS:  # decode windows are narrower than a page
                t = jnp.concatenate(
                    [t, jnp.zeros((PS - Tb, t.shape[-1]), jnp.float32)],
                    axis=0,
                )
            return pltpu.roll(t, -shift, 0)[:PS]

        for dma in reads:
            dma.wait()
        for j in range(lc):  # static unroll over the layer chunk
            rots = [rotated(new[j, 0]) for new in new_refs]
            for rot, slab in zip(rots, pages):
                slab[j] = jnp.where(sel, rot.astype(slab.dtype), slab[j])

        writes = [
            pltpu.make_async_copy(slab, out.at[lsl, page], sem)
            for out, slab, sem in zip(out_refs, pages, sems)
        ]
        for dma in writes:
            dma.start()
        for dma in writes:
            dma.wait()


#: bytes of a row's run of ONE layer a call of the K/V write kernel takes
#: a pool at the most. At twice that (2,048 tokens of 1,024, 4,096 of
#: 512) the kernel asks for 89.75 MB of scoped VMEM, which one program
#: grants and the next, 256 KB short, refuses (PERF.md section 6, PR 61)
RUN_BYTES = 2 << 20


def _layer_chunk(L: int, Tb: int, PS: int, KD: int, itemsize: int) -> int:
    """Largest divisor of L whose token blocks + page slabs fit a ~4 MiB
    VMEM budget per tensor."""
    budget = 4 << 20
    per_layer = (Tb + PS) * KD * itemsize
    lc = max(1, min(L, budget // max(per_layer, 1)))
    while L % lc:
        lc -= 1
    return lc


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("interpret",)
)
def kv_write_pallas(
    k_pages: jax.Array,   # [L, NP, PS, KD] fused page pool
    v_pages: jax.Array,
    k_new: jax.Array,     # [L, B, Tb, KD]
    v_new: jax.Array,
    page_table: jax.Array,  # [B, MP] int32
    start: jax.Array,       # [B] int32 — global position of token 0
    valid_len: jax.Array,   # [B] int32 — real tokens in the chunk
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    lowering.record_kernel("kv_write", interpret=interpret)
    # the kernel keeps a row's whole run of a layer in VMEM: a call takes
    # at most ``RUN_BYTES`` a layer, a POWER OF TWO of tokens (the chip's
    # dynamic roll over the 768 rows that 2 MiB hold of 1,280-wide rows
    # misplaced a prompt's later tokens: PERF.md section 6, PR 64), and a
    # longer chunk lands in several (8,192 tokens of 1,024 in eight)
    PS, KD = k_pages.shape[2:]
    T, fit = k_new.shape[2], RUN_BYTES // (KD * k_pages.dtype.itemsize)
    run = max(PS, (1 << (max(fit, 1).bit_length() - 1)) // PS * PS)
    for at in range(0, T, run):
        k_pages, v_pages = _write_pools(
            (k_pages, v_pages),
            (k_new[:, :, at:at + run], v_new[:, :, at:at + run]),
            page_table, start + at,
            jnp.clip(valid_len - at, 0, min(run, T - at)), interpret,
        )
    return k_pages, v_pages


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("interpret",)
)
def row_write_pallas(
    pages: jax.Array,     # [L, NP, PS, W]: ONE pool, a row a token
    new: jax.Array,       # [L, B, Tb, W]
    page_table: jax.Array,  # [B, MP] int32
    start: jax.Array,       # [B] int32
    valid_len: jax.Array,   # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """The same in-place write for a pool that has no V beside it (a
    model of latent layers, engine/kvcache.py): one slab a segment."""
    lowering.record_kernel("kv_write", interpret=interpret)
    return _write_pools(
        (pages,), (new,), page_table, start, valid_len, interpret
    )[0]


def _write_pools(pools, news, page_table, start, valid_len, interpret):
    """``news[i]`` [L, B, Tb, KD] written into ``pools[i]`` [L, NP, PS,
    KD] in place, the pools of one call through the same segments. The
    public wrappers count the write (``lowering.record_kernel``), once
    however many runs it lands in."""
    n = len(pools)
    L, NP, PS, KD = pools[0].shape
    _, B, Tb, _ = news[0].shape
    MP = page_table.shape[1]

    # per-(row, page) segments; a run of Tb tokens at any offset touches
    # at most ceil(Tb/PS)+1 pages
    S = (Tb + PS - 1) // PS + 1
    si = jnp.arange(S, dtype=jnp.int32)[None, :]          # [1, S]
    start = start.astype(jnp.int32)[:, None]              # [B, 1]
    end = start + valid_len.astype(jnp.int32)[:, None]
    pi = start // PS + si                                 # [B, S]
    page = jnp.take_along_axis(
        page_table.astype(jnp.int32), jnp.clip(pi, 0, MP - 1), axis=1
    )
    lo = jnp.maximum(start, pi * PS)
    hi = jnp.minimum(end, (pi + 1) * PS)
    rs = lo - pi * PS
    re = jnp.maximum(hi - pi * PS, rs)                    # empty => re==rs
    # page 0 is the garbage page: it backs padding rows' tables, and
    # clipped out-of-table indices may alias real entries — mask those
    # segments off entirely (re = rs)
    ok = jnp.logical_and(page > 0, pi < MP)
    re = jnp.where(ok, re, rs)
    shift = pi * PS - start                               # [B, S]
    row = jnp.broadcast_to(
        jnp.arange(B, dtype=jnp.int32)[:, None], (B, S)
    )

    lc = _layer_chunk(L, Tb, PS, KD, pools[0].dtype.itemsize)
    kernel = functools.partial(
        _kv_write_kernel, page_size=PS, layer_chunk=lc, pools=n
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    new_spec = pl.BlockSpec(
        (lc, 1, Tb, KD), lambda s, l, *refs: (l, refs[4][s], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B * S, L // lc),
        in_specs=[new_spec] * n + [any_spec] * n,
        out_specs=[any_spec] * n,
        scratch_shapes=[
            pltpu.VMEM((lc, PS, KD), pool.dtype) for pool in pools
        ] + [pltpu.SemaphoreType.DMA] * n,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in pools
        ],
        # flattened operands: scalars (0-4), the new rows (5 ..), then
        # the pools, each aliased to its output
        input_output_aliases={5 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        page.reshape(-1), rs.reshape(-1), re.reshape(-1),
        shift.reshape(-1), row.reshape(-1),
        *news, *pools,
    )
