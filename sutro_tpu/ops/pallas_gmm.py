"""Pallas TPU kernel: grouped matmul for expert-parallel MoE.

SURVEY §2.3 row 4 ("EP ... Pallas grouped-matmul kernel"): the MoE MLP's
hot op is E independent GEMMs whose row counts are data-dependent
(tokens routed per expert). ``jax.lax.ragged_dot`` is the always-correct
fallback; this kernel is the MXU-native path:

- lhs rows arrive SORTED BY EXPERT (ops/moe.py ragged path). Each group
  is padded (inside jit, outside the kernel) to a multiple of the row
  tile, so a row tile never spans two experts — the classic
  "megablox-lite" layout. Padding waste is < E*BM rows of zeros, which
  for prefill-sized token counts is small next to the E-fold waste of
  the dense path.
- grid ``(row_tiles, F // BF)``; each step multiplies one [BM, H] row
  tile by its expert's [H, BF] weight block, selected via a
  scalar-prefetched tile->expert map (the index map reads
  ``tile_expert[m]`` — one compiled kernel serves any routing).
- weights stream HBM->VMEM per tile via the BlockSpec pipeline; the MXU
  sees dense [BM, H] x [H, BF] tiles with f32 accumulation.

Expert parallelism composes outside: the expert axis of ``rhs`` is
sharded over the mesh "expert" axis and XLA inserts the all-to-alls
(parallel/sharding.py); inside each shard this kernel runs the local
experts' GEMMs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_M = 32
BLOCK_F = 128


def _gmm_kernel(tile_expert_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...],
        w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def grouped_matmul_supported(lhs: jax.Array, rhs: jax.Array) -> bool:
    """Static gate for the compiled TPU path (interpret mode bypasses).
    Requires M large relative to E*BLOCK_M: the padded layout wastes up
    to one row tile per expert, so decode-sized calls (M ~ B*top_k)
    would pay ~E times the FLOPs of exact ragged_dot — prefill-sized
    calls amortize the padding away."""
    M, H = lhs.shape
    E, _, F = rhs.shape
    return H % 128 == 0 and F % BLOCK_F == 0 and M >= E * BLOCK_M


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(
    lhs: jax.Array,          # [M, H] — rows sorted by group
    rhs: jax.Array,          # [E, H, F]
    group_sizes: jax.Array,  # [E] int32, sum == M
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns [M, F] with ``out[i] = lhs[i] @ rhs[g(i)]`` where ``g(i)``
    is row i's group. Same contract as ``jax.lax.ragged_dot``."""
    M, H = lhs.shape
    E, _, F = rhs.shape
    BM = BLOCK_M

    group_sizes = group_sizes.astype(jnp.int32)
    padded = ((group_sizes + BM - 1) // BM) * BM
    pcum = jnp.cumsum(padded)
    poffs = pcum - padded                                  # padded starts
    gcum = jnp.cumsum(group_sizes)
    gstart = gcum - group_sizes                            # true starts

    # scatter rows into the group-padded layout (zeros between groups)
    MP = ((M + E * BM + BM - 1) // BM) * BM                # static bound
    rows = jnp.arange(M, dtype=jnp.int32)
    row_group = jnp.searchsorted(gcum, rows, side="right").astype(jnp.int32)
    dest = poffs[row_group] + (rows - gstart[row_group])
    xpad = jnp.zeros((MP, H), lhs.dtype).at[dest].set(lhs)

    # tile -> expert map (tiles past the last group hit expert E-1 on
    # zero rows; their output is never gathered back)
    n_tiles = MP // BM
    tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * BM
    tile_expert = jnp.minimum(
        jnp.searchsorted(pcum, tile_start, side="right").astype(jnp.int32),
        E - 1,
    )

    out = pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles, F // BLOCK_F),
            in_specs=[
                pl.BlockSpec((BM, H), lambda m, f, te: (m, 0)),
                pl.BlockSpec(
                    (1, H, BLOCK_F), lambda m, f, te: (te[m], 0, f)
                ),
            ],
            out_specs=pl.BlockSpec(
                (BM, BLOCK_F), lambda m, f, te: (m, f)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((MP, F), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(tile_expert, xpad, rhs)
    return out[dest]
