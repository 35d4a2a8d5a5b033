"""Mixture-of-experts MLP.

Covers Qwen3-MoE (30b-a3b, 235b-a22b) and gpt-oss (20b/120b) from the
reference catalog (/root/reference/sutro/common.py:28-39), and the
LFM2-MoE router. Two execution
paths behind one call:

- ``dense``: computes every expert for every token and combines with the
  gate matrix. Correct and simple; the E/top_k FLOP overhead is fine for
  tiny test models and small E.
- ``ragged``: sorts the (token, expert) assignments by expert and runs
  the experts' grouped GEMMs over the sorted rows — the MXU-friendly path
  for large E. Static shapes: the expanded row count is exactly
  ``M = N * top_k``. Everything round the products is read off that ONE
  stable sort (``sort_rows``: sorted keys and the permutation at once; a
  sorted row's token is ``order // top_k``, nothing is gathered from a
  vector of scalars): the group sizes and the router's counts are a
  compare and sum over the keys (``rows_by_group``: no ``bincount``,
  which is a scatter-add, and no ``searchsorted``, which loops), and the
  rows return to token order by a GATHER through the sort's inverse and
  a float32 sum over ``top_k`` (``combine``), never a scatter: the
  chip's row scatter-add sorts its indices once more and gathers all
  ``[M, H]`` updates into that order before it scatters, and the pass
  ``y * prob`` in front of it was one more (PERF.md section 6, PR 44).
  Two row-indexed moves of ``[M, H]`` a layer are left: the gather in
  front of the products and the one behind them.

Router forms (``_route``, one definition for this file and
ops/moe_ep.py; ``ModelConfig.router_*`` says which):

- ``score="softmax"``: softmax over the top-k logits (equivalent to
  renormalized top-k of the full softmax — matches Qwen3's
  ``norm_topk_prob=True`` and gpt-oss); with ``renorm=False`` the top-k
  of the full softmax, as they are.
- ``score="sigmoid"``: per-expert sigmoid scores; the top-k is taken on
  the scores PLUS ``select_bias`` ([E], selection only), the weights
  are the chosen experts' unbiased scores, divided by their sum + 1e-6
  when ``renorm``, times ``scale``.

The grouped products (``_grouped``) are the Pallas kernel of
ops/pallas_gmm.py where the caller runs its kernels (``use_pallas``, the
engine's one switch, resolved once by the runner) and the shapes are on
the 128-lane grid, and ``jax.lax.ragged_dot`` otherwise. Both read the
experts where they lie: experts that arrive as the STACK of every routed
layer (``layer``: a model whose layers are of several kinds) are never
sliced (793 MB a layer at 64 x 3 x 2304 x 896 in bf16, every step). The
kernel indexes the flat stack from ``layer * E`` and fetches the experts
that have rows; ``ragged_dot`` is handed the stack with the other
layers' groups empty. ``ops/lowering.grouped_matmul_counts()`` says
which one a process traced: ``lowered`` / ``interpreted`` count the
kernel's traces, ``reference`` the ``use_pallas`` calls whose shapes sent
them to ``ragged_dot`` (PERF.md section 6, PR 28, 34 and 35).

Expert parallelism shards the expert axis of ``we_*`` over the mesh
"expert" axis; XLA turns the resulting gather/scatter into all-to-alls over
ICI (see parallel/sharding.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import lowering, pallas_gmm


def _grouped(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
    use_pallas: bool = False, layer: "jax.Array | None" = None,
    transposed: bool = False,
):
    """Grouped GEMM over rows sorted by group: the Pallas kernel when the
    caller runs its kernels and the shapes allow (ops/pallas_gmm.py),
    else ``jax.lax.ragged_dot``. With ``layer``, ``rhs`` is the flat
    stack ``[L*E, ...]`` and ``group_sizes`` that layer's ``[E]``.
    ``transposed``: each matrix of ``rhs`` lies output-major, [N, K]."""
    if use_pallas:
        # the kernel takes rows in whole sublanes: a few zero rows ride
        # the last group (6 rows: one row's top-6 in the numbers check)
        M = lhs.shape[0]
        pad = -M % 8
        rows = jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs
        if pallas_gmm.grouped_matmul_supported(rows, rhs, transposed):
            sizes = group_sizes.at[-1].add(pad) if pad else group_sizes
            out = pallas_gmm.grouped_matmul(
                rows, rhs, sizes, layer, transposed=transposed
            )
            return out[:M] if pad else out
        lowering.record_reference(lowering.GROUPED)
    if transposed:
        # ``ragged_dot`` wants each matrix input-major: this layer's
        # experts alone, transposed (a copy of them a call: the path of
        # a CPU and of ``use_pallas`` off), behind a barrier: the TPU
        # compiler fails on a ragged dot it has folded a transpose into
        if layer is not None:
            E = group_sizes.shape[0]
            rhs = jax.lax.dynamic_slice_in_dim(rhs, layer * E, E, axis=0)
            layer = None
        rhs = jax.lax.optimization_barrier(jnp.swapaxes(rhs, -1, -2))
    if layer is not None:
        # the whole stack, the other layers' groups empty
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), jnp.int32), group_sizes,
            (layer * group_sizes.shape[0],),
        )
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _route(
    xt: jax.Array,        # [N, H]
    router: jax.Array,    # [H, E]
    router_b,             # [E] or None
    top_k: int,
    *,
    score: str = "softmax",
    select_bias=None,     # [E] or None: added for the top-k only
    renorm: bool = True,
    scale: float = 1.0,
    renorm_eps: float = 1e-6,
):
    """Shared routing: fp32 logits -> top-k -> weights (module
    docstring: the router's forms), plus the flattened [N*top_k]
    expansion (token, expert, prob) used by the grouped-GEMM paths. One
    definition so the EP path (ops/moe_ep.py) can never diverge from
    the single-device reference. (The ragged path reads ``top_idx``,
    ``probs`` and ``flat_expert``: its one sort gives a sorted row's
    token as ``order // top_k``, so ``flat_token`` and ``flat_prob``
    are for a caller that wants the expansion whole.)"""
    N = xt.shape[0]
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)  # [N, E]
    if router_b is not None:
        logits = logits + router_b.astype(jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen_by = scores
        if select_bias is not None:
            chosen_by = scores + select_bias.astype(jnp.float32)
        _, top_idx = jax.lax.top_k(chosen_by, top_k)              # [N, K]
        probs = jnp.take_along_axis(scores, top_idx, axis=-1)
        if renorm:
            probs = probs / (jnp.sum(probs, axis=-1, keepdims=True) + renorm_eps)
    elif score != "softmax":
        raise ValueError(f"unknown router score {score!r}")
    elif select_bias is not None:
        raise ValueError("a selection bias goes with sigmoid scores")
    elif renorm:
        top_logits, top_idx = jax.lax.top_k(logits, top_k)        # [N, K]
        probs = jax.nn.softmax(top_logits, axis=-1)
    else:
        probs, top_idx = jax.lax.top_k(
            jax.nn.softmax(logits, axis=-1), top_k
        )
    if scale != 1.0:
        probs = probs * scale
    M = N * top_k
    flat_expert = top_idx.reshape(M)
    flat_token = jnp.repeat(jnp.arange(N), top_k)
    flat_prob = probs.reshape(M)
    return top_idx, probs, flat_expert, flat_token, flat_prob


def sort_rows(key: jax.Array):
    """The ONE sort a routed layer makes: the expanded rows by ``key``
    [M], stable (token order is kept inside a group). Returns ``(sorted
    key, order)``: ``order[i]`` is the expanded row (``token * top_k +
    choice``) that lies at sorted place ``i``, so a sorted row's token
    is ``order // top_k`` and nothing is gathered from a vector of
    scalars afterwards."""
    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, iota), num_keys=1, is_stable=True)


def rows_by_group(key: jax.Array, groups: int) -> jax.Array:
    """How many of ``key`` [M] equal each of ``0 .. groups``, [groups]
    int32, by compare and sum: one fusion of ``groups * M`` compares on
    the chip, where ``jnp.bincount`` is a scatter-add of M scalars and
    ``jnp.searchsorted``'s default a loop of log M trips."""
    return jnp.sum(
        key[None, :] == jnp.arange(groups, dtype=key.dtype)[:, None],
        axis=1, dtype=jnp.int32,
    )


def held_key(expert: jax.Array, first, held: int) -> jax.Array:
    """An expert's LOCAL index in a layer that holds experts ``first ..
    first + held``, and ``held`` where it is not this layer's."""
    loc = expert - first
    return jnp.where(jnp.logical_and(loc >= 0, loc < held), loc, held)


def held_weights(top_idx, probs, first, held: int) -> jax.Array:
    """``probs`` [N, top_k] with 0 on a choice that is not this
    layer's: what ``combine`` weighs a held share's rows by."""
    return jnp.where(held_key(top_idx, first, held) < held, probs, 0.0)


def held_rows(flat_expert, first, held: int):
    """The expanded rows as a layer that holds experts ``first ..
    first + held`` takes them: its own rows first, grouped by LOCAL
    expert (a stable sort keeps token order), the others after. Returns
    ``(local [M], order [M], group_sizes [held])``, sorted
    (``sort_rows``: a row's token is ``order // top_k``): ``local`` is
    ``held`` on a row that is not this layer's, and its weight is 0
    (``held_weights``). Static shapes, no capacity factor, no dropped
    token: the tail rides the LAST held group (``local`` clipped to it
    indexes a bias) and the caller zeroes its inputs, so what it adds is
    exactly nothing."""
    key = held_key(flat_expert, first, held)
    local, order = sort_rows(key)
    # the tail counts onto the last held group: its key, clipped
    return local, order, rows_by_group(jnp.minimum(key, held - 1), held)


def combine(y: jax.Array, order: jax.Array, weights: jax.Array) -> jax.Array:
    """UNPERMUTE: the experts' sorted rows ``y`` back to their tokens,
    [N, H]. ``weights`` [N, top_k] is each choice's weight in token
    order (0 on a choice that is not this layer's), ``order`` the sort's
    permutation. Its inverse (a second sort, of ``(order, iota)``: not a
    scatter of ``iota``) gathers the rows back token-major, and a
    token's ``top_k`` rows are weighted and summed in float32, cast
    once. No scatter: the chip's row scatter-add sorts its indices
    again, gathers the updates into that order and only then scatters.

    ``y`` may hold the first ``R < M`` sorted rows alone (a capped
    share: the rows behind them are not this layer's and weigh 0): they
    are read from ONE zero row behind ``y``, so what they add is exactly
    nothing. On the chip that gather of ``M`` rows, seven of eight of
    them the same row, takes what the scatter-add of the ``R`` live rows
    took at a 4,096-token prefill and 0.78 of it at 2,048
    (benchmarks/moe_combine_ab.py; PERF.md section 6, PR 44)."""
    N, top_k = weights.shape
    R, H = y.shape
    M = N * top_k
    lowering.record_moe_combine("unpermuted")
    iota = jnp.arange(M, dtype=jnp.int32)
    _, inverse = jax.lax.sort((order, iota), num_keys=1)
    if R < M:
        y = jnp.concatenate([y, jnp.zeros((1, H), y.dtype)])
        inverse = jnp.minimum(inverse, R)
    back = y[inverse].reshape(N, top_k, H).astype(jnp.float32)
    out = jnp.sum(back * weights.astype(jnp.float32)[:, :, None], axis=1)
    return out.astype(y.dtype)


def _share_row_cap(M: int, held: int, experts: int, even_shares: int = 2):
    """Rows of ``M`` expanded rows a held share's products take when its
    own rows fit them, or None (all rows): ``even_shares`` times (twice)
    the share an even router sends it, in whole tiles of 512, where that
    is under half the rows. A share of a half (or a decode step's few
    rows) gives None, and the program it had."""
    cap = -(-even_shares * M * held // experts // 512) * 512
    return cap if 2 * cap <= M else None


def relu2(x: jax.Array) -> jax.Array:
    """``relu(x)^2``, squared in float32."""
    r = jax.nn.relu(x.astype(jnp.float32))
    return (r * r).astype(x.dtype)


def _hidden(gate, up: jax.Array, activation: str) -> jax.Array:
    """An expert's hidden row: ``act(gate) * up``, or, for an expert of
    two matrices (``gate`` None), ``relu(up)^2``."""
    if gate is not None:
        a, u = _act(gate, up, activation)
        return a * u
    if activation != "relu2":
        raise ValueError(
            f"an expert of two matrices takes relu2, not {activation!r}"
        )
    return relu2(up)


def _each(experts, fn):
    """``fn`` over (gate, up, down); an absent gate stays None."""
    return [None if w is None else fn(w) for w in experts]


def _act(gate: jax.Array, up: jax.Array, activation: str):
    if activation == "gelu":
        a = jax.nn.gelu(gate.astype(jnp.float32), approximate=True)
        return a.astype(gate.dtype), up
    if activation == "swiglu_oss":
        g = jnp.clip(gate.astype(jnp.float32), max=7.0)
        a = (g * jax.nn.sigmoid(1.702 * g)).astype(gate.dtype)
        u = jnp.clip(up.astype(jnp.float32), -7.0, 7.0).astype(up.dtype) + 1.0
        return a, u
    a = jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype)
    return a, up


def moe_mlp(
    x: jax.Array,          # [B, T, H]
    router: jax.Array,     # [H, E]
    we_gate,               # [E, H, F]; None: experts of two matrices
    we_up: jax.Array,      # [E, H, F]; [E, F, H] where ``we_gate`` is None
    we_down: jax.Array,    # [E, F, H]
    *,
    top_k: int,
    activation: str = "silu",
    method: str = "auto",
    first_expert: int = 0,
    router_b: "jax.Array | None" = None,   # [E]
    bias_gate: "jax.Array | None" = None,  # [E, F]  (gpt-oss)
    bias_up: "jax.Array | None" = None,    # [E, F]
    bias_down: "jax.Array | None" = None,  # [E, H]
    route: "dict | None" = None,  # ``_route``'s keywords: the router's form
    use_pallas: bool = False,
    return_counts: bool = False,
    layer: "jax.Array | None" = None,
    share_rows: int = 2,   # ``_share_row_cap``'s even shares
    token_tile: int = 0,   # most tokens the layer takes at once (0: all)
):
    """The routed MLP over ``x``. With ``return_counts`` also the rows
    each expert got, [E] int32 over the ROUTER's experts, held here or
    not (every row of ``x`` counts, padding too: it is what the grouped
    products compute).

    ``we_*`` with fewer experts than the router has outputs are a held
    share that starts at ``first_expert`` (module docstring); it takes
    the ragged path at every size, whose sort is its definition
    (``held_rows``). The sort puts the held experts' rows first; a share
    under a quarter over many rows (a prefill: ``_share_row_cap``) runs
    its products over those rows alone where they fit ``share_rows``
    times (twice) its even share, since at a sixteenth the zero rows
    that ride the last group were fifteen of sixteen (215 of a
    4,096-token prefill's 358 ms: PERF.md section 6, PR 42); where they
    do not fit, over EVERY row, so the room is what an uneven router
    costs nothing under (``ModelConfig.moe_share_rows``).

    With ``layer`` (scalar int32) the ``we_*`` are the STACKS of every
    routed layer, [L, E, H, F] / [L, E, F, H], and this layer's experts
    are groups ``layer*E .. (layer+1)*E`` of the stack seen flat,
    [L*E, ...]: the grouped products take the whole stack and the
    layer's index (``_grouped``). A ``we[layer]`` slice would reach them
    as a copy of the layer's experts (1.2 GB a layer at 64 x 2048 x 1536
    in bf16, every step): a grouped product reads its right-hand side
    in place and cannot take a slice fused into it."""
    B, T, H = x.shape
    E = router.shape[-1]
    N = B * T
    if token_tile and N > token_tile:
        # at most ``token_tile`` tokens at a time, one EQUAL tile after
        # another (``ModelConfig.moe_token_tile``): every temporary of
        # the layer is then a tile's, whatever the dispatch holds, and
        # each touched expert is streamed once a tile
        tiles = -(-N // token_tile)
        while N % tiles:
            tiles += 1

        def one(tile):
            return moe_mlp(
                tile, router, we_gate, we_up, we_down, top_k=top_k,
                activation=activation, method=method,
                first_expert=first_expert, router_b=router_b,
                bias_gate=bias_gate, bias_up=bias_up, bias_down=bias_down,
                route=route, use_pallas=use_pallas, return_counts=True,
                layer=layer, share_rows=share_rows,
            )

        outs, counts = jax.lax.map(one, x.reshape(tiles, 1, N // tiles, H))
        out = outs.reshape(B, T, H)
        return (out, jnp.sum(counts, axis=0)) if return_counts else out
    xt = x.reshape(N, H)
    held = we_up.shape[-3]
    share = held != E
    if method == "auto":
        method = "dense" if E <= 8 and not share else "ragged"
    if layer is not None and method == "dense":
        # small E (the dense path): one layer's experts, sliced
        we_gate, we_up, we_down = _each(
            (we_gate, we_up, we_down), lambda w: w[layer]
        )
        layer = None

    top_idx, probs, flat_expert, _, _ = _route(
        xt, router, router_b, top_k, **(route or {})
    )

    def result(out):
        if not return_counts:
            return out
        return out, rows_by_group(flat_expert, E)

    if method == "dense":
        gates = jnp.zeros((N, E), jnp.float32)
        gates = gates.at[jnp.arange(N)[:, None], top_idx].add(probs)
        if share:
            gates = gates[:, first_expert : first_expert + held]
        g = None
        if we_gate is None:
            u = jnp.einsum("nh,efh->nef", xt, we_up)
        else:
            u = jnp.einsum("nh,ehf->nef", xt, we_up)
            g = jnp.einsum("nh,ehf->nef", xt, we_gate)
        if bias_gate is not None:
            g = g + bias_gate[None].astype(g.dtype)
            u = u + bias_up[None].astype(u.dtype)
        y = jnp.einsum("nef,efh->neh", _hidden(g, u, activation), we_down)
        if bias_down is not None:
            y = y + bias_down[None].astype(y.dtype)
        out = jnp.einsum("ne,neh->nh", gates.astype(y.dtype), y)
        return result(out.reshape(B, T, H))

    # ragged grouped-GEMM path: what lies round the products is read off
    # ONE sort of the expanded rows (``sort_rows``, ``combine``)
    M = N * top_k
    cap = None
    if share:
        sorted_expert, order, group_sizes = held_rows(
            flat_expert, first_expert, held
        )
        weights = held_weights(top_idx, probs, first_expert, held)
        cap = _share_row_cap(M, held, E, share_rows)
    else:
        sorted_expert, order = sort_rows(flat_expert)
        group_sizes = rows_by_group(flat_expert, E)
        weights = probs
    if layer is not None:  # the stacks seen flat: a bitcast
        we_gate, we_up, we_down = _each(
            (we_gate, we_up, we_down),
            lambda w: w.reshape((-1,) + w.shape[2:]),
        )

    def through(rows, group_sizes):
        """The experts over the first ``rows`` sorted rows, summed back
        a token."""
        local = sorted_expert[:rows]
        lhs = xt[order[:rows] // top_k]                   # [rows, H]
        if share:
            lhs = lhs * (local < held)[:, None].astype(xt.dtype)
            local = jnp.minimum(local, held - 1)
        grouped = functools.partial(
            _grouped, group_sizes=group_sizes, use_pallas=use_pallas,
            layer=layer,
        )
        g = None if we_gate is None else grouped(lhs, we_gate)  # [rows, F]
        u = grouped(lhs, we_up, transposed=we_gate is None)
        if bias_gate is not None:
            g = g + bias_gate[local].astype(g.dtype)
            u = u + bias_up[local].astype(u.dtype)
        y = grouped(_hidden(g, u, activation), we_down)       # [rows, H]
        if bias_down is not None:
            y = y + bias_down[local].astype(y.dtype)
        return combine(y, order, weights)

    if cap is None:
        out = through(M, group_sizes)
    else:
        # a small share of many rows: the held experts' rows come first
        # in the sort, and the first ``cap`` rows hold them all unless
        # the router sent this chip over twice its even share (then
        # every row, as above). What rides the last group is zeros
        # either way, so the sums are the same
        out = jax.lax.cond(
            jnp.sum(sorted_expert < held) <= cap,
            lambda: through(cap, group_sizes.at[held - 1].add(cap - M)),
            lambda: through(M, group_sizes),
        )
    return result(out.reshape(B, T, H))
