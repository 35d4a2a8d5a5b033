"""Pallas TPU kernel: a decode step's read of the committed Mamba-2 state.

``yS[b, t, i] = sum_n S[layer, slots[b], n, i] * C[b, t, group(i), n]``
in float32: what ``models/transformer.ssd_pending`` adds to a short
chunk's own tokens for the state the chunk does not advance. The XLA
expression there stays the fallback and the specification. What the
kernel does that the expression does not (PERF.md section 6, PR 43):

- **The pool is read where it lies.** The operand is the whole stacked
  pool ``[L_m, NS, N, I]`` and the layer a prefetched scalar: a slice
  ``pool[layer]`` handed to a custom call is a copy of a layer's pool
  (269 MB at 257 slots) a layer a step.
- **A grid over ROWS, one contiguous block a row.** ``slots`` is
  prefetched into the block index, so a grid step streams its row's
  ``[N, I]`` (1 MB of bf16 in one run) through the BlockSpec pipeline
  and stores that row's ``[T, I]``: no inversion of ``slots``, no
  ``C[row]``, no gather of the result, and a batch smaller than the
  pool reads fewer bytes. Chosen over slot-major blocks of several
  slots by measurement: two, four and eight rows a grid step (the pool
  handed to the call once a row) read the same 640 GB/s as one (my chip
  run, PR 43), so what bounds the call is the stream of fetches and not
  the grid's fixed cost a row, which is all that larger blocks save.
- **The groups are a loop inside the block** over its ``I / G``-channel
  lane ranges: eight groups read the block once, as one does.
- **The same precision.** The state keeps its stored dtype and ``C`` is
  float32; every product and the sum over ``N`` are float32, as in the
  expression. Two forms (``form``), timed alone by
  ``benchmarks/ssm_state_read_ab.py`` (0.420 / 0.424 ms a read of 256
  rows at one token, 0.45 / 0.60 ms at four: neither holds the fetches
  back at one token, and the MXU's work grows slower with the chunk):

  - ``"mxu"`` (a bf16 pool): ``C`` is split EXACTLY into three bf16
    terms (hi + mid + lo is the float32 value: 3 x 8 bits of mantissa),
    a group's ``[3T, N] x [N, I/G]`` runs in bf16 with float32
    accumulation and the three terms' rows are summed. A product of two
    bf16 values is exact in float32, so the result differs from the
    expression's by the order of a float32 sum alone. No convert of the
    state: the v5e has no bf16 VALU.
  - ``"vpu"`` (any pool; a float32 pool's only form): the block is
    converted to float32 in VMEM, multiplied by ``C`` down the sublanes
    and reduced over them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lowering

#: the widest chunk (a verify chunk's tokens) a call takes: beyond it
#: the XLA expression. The kernel's work a row grows with T and its
#: bytes do not; no cell runs a verify chunk over a Mamba model
MAX_TOKENS = 16


def state_read_supported(pool: jax.Array, c: jax.Array, groups: int) -> bool:
    """Static gate: a bf16 or float32 pool whose channels a group (so
    all of them) are on the 128-lane grid, ``N`` in whole sublane packs
    of the pool's dtype, a float32 ``C`` of at most ``MAX_TOKENS``
    tokens."""
    N, I = pool.shape[-2:]
    if pool.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    pack = 32 // pool.dtype.itemsize
    return (
        pool.ndim == 4 and c.ndim == 3 and c.dtype == jnp.float32
        and I % groups == 0 and (I // groups) % 128 == 0 and N % pack == 0
        and c.shape[-1] == groups * N and 0 < c.shape[1] <= MAX_TOKENS
    )


def split_bf16(c: jax.Array):
    """A float32 array as three bf16 arrays whose float32 sum is the
    array, exactly: each takes the next 8 bits of the mantissa."""
    hi = c.astype(jnp.bfloat16)
    rest = c - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(layer, slots, c_ref, s_ref, o_ref, *, groups, form):
    del layer, slots  # the index maps'
    T = c_ref.shape[0]
    N, I = s_ref.shape
    w = I // groups
    for g in range(groups):
        ch = slice(g * w, (g + 1) * w)
        c = c_ref[:, g * N : (g + 1) * N]                     # [T, N] f32
        if form == "mxu":
            # rows [hi | mid | lo]: one pass of the state's block through
            # the MXU for the three, each product exact in float32
            terms = jnp.concatenate(split_bf16(c), axis=0)    # [3T, N]
            y = jnp.dot(
                terms, s_ref[:, ch], preferred_element_type=jnp.float32
            )                                                 # [3T, w]
            o_ref[:, ch] = y[:T] + y[T : 2 * T] + y[2 * T :]
        else:
            s = s_ref[:, ch].astype(jnp.float32)              # [N, w]
            # C down the sublanes: [T, N] -> [N, T], a column a token
            cols = c.T
            for t in range(T):
                o_ref[t : t + 1, ch] = jnp.sum(
                    s * cols[:, t : t + 1], axis=0, keepdims=True
                )


@functools.partial(
    jax.jit, static_argnames=("groups", "form", "interpret", "counted")
)
def ssm_state_read(
    pool: jax.Array,    # [L_m, NS, N, I]: every mamba layer's slots
    layer,              # scalar int32: the layer read
    slots: jax.Array,   # [B] int32: each row's slot
    c: jax.Array,       # [B, T, G*N] float32
    *,
    groups: int,
    form: "str | None" = None,
    interpret: bool = False,
    counted: str = lowering.SSM_STATE_READ,
) -> jax.Array:
    """Returns ``[B, T, I]`` float32 with ``out[b, t, i] = sum_n
    pool[layer, slots[b], n, i] * c[b, t, g(i) * N + n]``, ``g(i)`` the
    group of ``I / groups`` channels that holds ``i``. ``form`` None
    picks by the pool's dtype (module docstring). ``counted``: the name
    the trace is counted and the kernel is called under (a delta-rule
    layer's read, a head a group: ``lowering.KDA_STATE_READ``)."""
    lowering.record_kernel(counted, interpret=interpret)
    _, _, N, I = pool.shape
    B, T, _ = c.shape
    if form is None:
        form = "mxu" if pool.dtype == jnp.bfloat16 else "vpu"
    block = N * I * pool.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, groups=groups, form=form),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, T, groups * N), lambda b, l, s: (b, 0, 0)),
                pl.BlockSpec(
                    (None, None, N, I), lambda b, l, s: (l[0], s[b], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((None, T, I), lambda b, l, s: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, I), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of the state's block, its float32 copy in the
            # "vpu" form, and the small operands
            vmem_limit_bytes=max(2 * block + N * I * 4 + (8 << 20), 32 << 20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * T * N * I,
            bytes_accessed=B * (block + T * (groups * N + I) * 4),
            transcendentals=0,
        ),
        name=counted,
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        slots.astype(jnp.int32), c, pool,
    )


# ---------------------------------------------------------------------------
# A delta-rule layer's commit of a chunk's accepted tokens
# ---------------------------------------------------------------------------


def state_commit_supported(pool: jax.Array) -> bool:
    """Static gate of ``kda_state_commit``: a bf16 or float32 pool
    ``[L, NS, dk, H * dk]`` whose heads are whole 128-lane tiles (so
    ``dk`` is whole sublane packs of either dtype too)."""
    if pool.ndim != 4 or pool.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    N, I = pool.shape[-2:]
    return N % 128 == 0 and I % N == 0


def _commit_kernel(layer, slots, d_ref, k_ref, u_ref, s_ref, o_ref):
    del layer, slots  # the index maps'
    N, I = s_ref.shape
    W = k_ref.shape[0]
    for h in range(I // N):
        ch = slice(h * N, (h + 1) * N)
        # the head's decay and decayed keys DOWN the sublanes: [N, 1 + W]
        cols = jnp.concatenate([d_ref[:, ch], k_ref[:, ch]], axis=0).T
        new = s_ref[:, ch].astype(jnp.float32) * cols[:, 0:1]
        for w in range(W):
            new = new + cols[:, 1 + w : 2 + w] * u_ref[w : w + 1, ch]
        o_ref[:, ch] = new.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_state_commit(
    pool: jax.Array,    # [L_k, NS, dk, I]: every kda layer's slots
    layer,              # scalar int32: the layer advanced
    slots: jax.Array,   # [B] int32: each row's slot (0: the garbage slot)
    decay: jax.Array,   # [B, I] float32: exp(G_n) a channel of the KEY axis
    coef: jax.Array,    # [B, W, I] float32: k_i * exp(G_n - G_i), 0 past n
    u: jax.Array,       # [B, W, I] float32
    *,
    interpret: bool = False,
) -> jax.Array:
    """``pool[layer, slots[b]]`` <- ``Diag(decay_b) S + sum_w coef_b,w
    u_b,w^T`` a head, in place: a grid over ROWS, the row's slot
    ``[dk, I]`` streamed in through the BlockSpec pipeline and out to the
    same block (the pool is aliased to the result), every product and sum
    float32 on the VPU (a head's ``[dk, W]`` keys times ``[W, dv]``
    updates: W outer products, exact in float32; the MXU's float32
    passes are not). Rows whose slot is 0 write the garbage slot. A
    row's ``decay`` and ``coef`` are indexed ``[h * dk + k]``: the head's
    KEY axis, which the kernel turns down the sublanes."""
    lowering.record_kernel(lowering.KDA_STATE_COMMIT, interpret=interpret)
    _, _, N, I = pool.shape
    B, W, _ = coef.shape
    pad = -W % 8
    if pad:  # whole sublane tiles of float32
        coef, u = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (coef, u))
        W += pad
    block = N * I * pool.dtype.itemsize

    def row(b, l, s):
        return (b, 0, 0)

    def slot(b, l, s):
        return (l[0], s[b], 0, 0)

    return pl.pallas_call(
        _commit_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, 1, I), row),
                pl.BlockSpec((None, W, I), row),
                pl.BlockSpec((None, W, I), row),
                pl.BlockSpec((None, None, N, I), slot),
            ],
            out_specs=pl.BlockSpec((None, None, N, I), slot),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands: layer, slots, decay, coef, u, pool
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(4 * block + (16 << 20), 32 << 20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * (W + 1) * N * I,
            bytes_accessed=B * (2 * block + (2 * W + 1) * I * 4),
            transcendentals=0,
        ),
        name="kda_state_commit",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
        decay[:, None, :], coef, u, pool,
    )
