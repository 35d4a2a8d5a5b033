"""Pallas TPU kernel: the routed experts' grouped product.

``out[i] = lhs[i] @ rhs[group(i)]`` for rows that arrive SORTED BY GROUP
(ops/moe.py's ragged path, ops/moe_ep.py): the contract of
``jax.lax.ragged_dot`` for group sizes that sum to the row count, which
stays the fallback. What the kernel does that ``ragged_dot`` on the TPU
does not (PERF.md section 6, PR 35):

- **Rows stay where they are.** Row tiles of ``tm`` rows over ``[M, K]``;
  the grid walks the (row tile, group) pairs that intersect, VISITS, from
  scalar-prefetched metadata built from ``group_sizes`` inside the jit
  (``_visits``; the megablox scheme). A tile that spans several groups is
  visited once a group and stores that group's rows alone (a select on
  the store); a visit multiplies only the sub-tiles of ``ts`` rows its
  group has rows in, so a large tile re-reads few weights and computes
  little more than a small one. No padded copy of ``lhs``, no gather
  back.
- **The expert stack is read in place.** ``group_sizes`` is ONE layer's
  ``[E]``; ``rhs`` may be the flat stack of every routed layer's experts,
  ``[L*E, K, N]``, and ``layer`` (a prefetched scalar) offsets the
  block index by ``layer * E``. Only the experts a visit names are ever
  fetched: an empty group costs nothing, another layer's experts are
  never touched. ``layer=None`` is offset 0.
- **One tile policy, from the static shape** (``_tiles``): each visit
  streams one expert's ``[K, tn]`` block HBM->VMEM through the BlockSpec
  pipeline (double-buffered; ``tn = N`` where the block fits, so the
  fetch is one contiguous run) and multiplies in the operands' dtype
  with float32 accumulation.

The grid is static, ``tiles + E - 1`` visits, the most there can be; the
visits past the last real one repeat its block indices (no fetch) and
compute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lowering

#: rows a visit multiplies at a time: the MXU's height
SUB_ROWS = 128
#: bytes one buffer of an expert's ``[K, tn]`` block may take in VMEM
RHS_BLOCK_BYTES = 8 << 20
#: and where the width has no legal halving (off the 128 grid), what one
#: buffer of the expert's WHOLE matrix may take: two of them, the row
#: tiles and the float32 product stay under 48 MiB of a v5e core's 128
WHOLE_RHS_BYTES = 16 << 20


def grouped_matmul_supported(
    lhs: jax.Array, rhs: jax.Array, transposed: bool = False
) -> bool:
    """Static gate: rows in whole sublanes, operands of one dtype the
    MXU takes, and the expert's matrix on the 128-lane grid both ways,
    or off it in whole sublane packs and small enough to be ONE block
    (``WHOLE_RHS_BYTES``: a block's dimension must be on the grid or
    the array's whole). A width N off the grid is taken only where the
    matrix lies output-major (``transposed``: ``rhs`` is ``[G, N, K]``):
    the device keeps an array's ALIGNED axis minor and hands a custom
    call its operand minor-last, so ``[G, K, N]`` would be copied,
    whole, every call."""
    M, K = lhs.shape
    if transposed:
        N, Kr = rhs.shape[-2:]
    else:
        Kr, N = rhs.shape[-2:]
    on_grid = K % 128 == 0 and N % 128 == 0
    one_block = (
        K % 16 == 0 and N % 16 == 0
        and (N % 128 == 0 or transposed)
        and K * N * lhs.dtype.itemsize <= WHOLE_RHS_BYTES
    )
    return (
        (on_grid or one_block) and Kr == K and M % 8 == 0 and M > 0
        and lhs.dtype == rhs.dtype
        and lhs.dtype in (jnp.bfloat16, jnp.float32)
    )


def _tiles(M: int, K: int, N: int, itemsize: int):
    """``(tm, ts, tn)`` from the static shape. Decode sizes (a few rows a
    group) take one sub-tile a tile: every touched expert is read once
    and the visits stay near the groups. Prefill sizes take tiles of four
    sub-tiles: the visits stay near ``tiles + groups`` and the weights'
    re-read under 2x, while a visit computes only the sub-tiles its
    group reaches."""
    if M <= SUB_ROWS:
        tm = ts = M
    else:
        ts = SUB_ROWS
        tm = ts * (4 if M >= 4096 else 1)
    tn = N
    while K * tn * itemsize > RHS_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    if K * tn * itemsize > RHS_BLOCK_BYTES:
        # a block that has no legal halving (2,688 = 21 x 128, or a
        # matrix off the grid, taken whole): small row tiles beside it
        tm = ts
    return tm, ts, tn


def _visits(group_sizes: jax.Array, M: int, tm: int):
    """The (row tile, group) pairs that intersect, in row order:
    ``(offsets [E+1], group [V], tile [V], count [1])`` with
    ``V = tiles + E - 1``. Entries past ``count`` repeat the last visit."""
    E = group_sizes.shape[0]
    tiles = pl.cdiv(M, tm)
    V = tiles + E - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    per_group = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(per_group)
    count = visit_end[-1]
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(visit_end, v, side="right", method="compare_all"),
        E - 1,
    ).astype(jnp.int32)
    tile = first[group] + v - (visit_end[group] - per_group[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, count[None]


def _kernel(offsets, group, tile, count, base, x_ref, w_ref, o_ref, *, ts,
            transposed):
    del base  # the index maps' (this layer's first group in the stack)
    v = pl.program_id(1)
    tm, tn = o_ref.shape

    @pl.when(v < count[0])
    def _visit():
        g = group[v]
        lo, hi = offsets[g], offsets[g + 1]
        row0 = tile[v] * tm
        for s in range(tm // ts):
            r0 = row0 + s * ts
            rows = slice(s * ts, (s + 1) * ts)

            @pl.when(jnp.logical_and(r0 < hi, r0 + ts > lo))
            def _sub_tile():
                acc = jax.lax.dot_general(
                    x_ref[rows, :], w_ref[...],
                    (((1,), (1 if transposed else 0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                row = r0 + jax.lax.broadcasted_iota(jnp.int32, (ts, tn), 0)
                mine = jnp.logical_and(row >= lo, row < hi)
                # the other groups' rows of this tile keep what their own
                # visits stored (or will store over what lies here now)
                o_ref[rows, :] = jnp.where(
                    mine, acc, o_ref[rows, :].astype(jnp.float32)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "transposed"))
def grouped_matmul(
    lhs: jax.Array,          # [M, K]: rows sorted by group
    rhs: jax.Array,          # [G, K, N], G >= (layer + 1) * E
    group_sizes: jax.Array,  # [E] int32, sum == M
    layer: "jax.Array | None" = None,  # scalar int32: groups layer*E ..
    *,
    interpret: bool = False,
    transposed: bool = False,
) -> jax.Array:
    """Returns ``[M, N]`` with ``out[i] = lhs[i] @ rhs[layer*E + g(i)]``
    where ``g(i)`` is row i's group by ``group_sizes``; every row belongs
    to a group (the sizes sum to ``M``). ``transposed``: ``rhs`` is
    ``[G, N, K]``, each expert's matrix output-major, and the product
    contracts both operands' minor axis (``lhs[i] @ rhs[g].T``): how a
    matrix whose width N is off the 128-lane grid is read where it lies
    (the device keeps an array's aligned axis minor, and a custom call's
    operand minor-last: ``[G, K, N]`` would be copied, whole, a call)."""
    lowering.record_kernel(lowering.GROUPED, interpret=interpret)
    M, K = lhs.shape
    N = rhs.shape[-2] if transposed else rhs.shape[-1]
    E = group_sizes.shape[0]
    tm, ts, tn = _tiles(M, K, N, lhs.dtype.itemsize)
    group_sizes = group_sizes.astype(jnp.int32)
    offsets, group, tile, count = _visits(group_sizes, M, tm)
    base = (
        jnp.zeros((1,), jnp.int32) if layer is None
        else (jnp.asarray(layer, jnp.int32) * E)[None]
    )
    itemsize = lhs.dtype.itemsize
    # two buffers a block, the float32 product and what the select reads
    vmem = (
        2 * (tm * K + K * tn + tm * tn) * itemsize + 3 * ts * tn * 4
    )
    return pl.pallas_call(
        functools.partial(_kernel, ts=ts, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # the column blocks outermost: an output block's visits are
            # consecutive, so its groups' stores meet in VMEM
            grid=(N // tn, group.shape[0]),
            in_specs=[
                pl.BlockSpec(
                    (tm, K), lambda n, v, o, g, t, c, b: (t[v], 0)
                ),
                pl.BlockSpec(
                    (None, tn, K),
                    lambda n, v, o, g, t, c, b: (b[0] + g[v], n, 0),
                ) if transposed else pl.BlockSpec(
                    (None, K, tn),
                    lambda n, v, o, g, t, c, b: (b[0] + g[v], 0, n),
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, v, o, g, t, c, b: (t[v], n)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem + (8 << 20), 32 << 20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N,
            bytes_accessed=(
                M * K * (N // tn) + group.shape[0] * K * N + M * N
            ) * itemsize,
            transcendentals=0,
        ),
        name="grouped_matmul",
        interpret=interpret,
    )(offsets, group, tile, count, base, lhs, rhs)
