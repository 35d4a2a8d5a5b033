"""Explicit expert-parallel MoE execution (shard_map over the mesh).

Why this exists: `ops/moe.py` under plain GSPMD works, but its ragged
path gathers tokens by a data-dependent permutation and feeds
`ragged_dot` group sizes for ALL experts — the partitioner's only safe
lowering is to all-gather the expert weights onto every shard. For the
models EP exists for (qwen-3-235b-a22b, gpt-oss-120b — reference
catalog /root/reference/sutro/common.py:28-39), replicating expert
weights is exactly the thing that cannot happen: weight residency
1/ep-per-shard IS the point (SURVEY §2.3 "EP expert parallelism").

This path makes the partitioning manual and exact:

- shard_map over the engine mesh; expert weights arrive pre-sharded
  ``[E/ep, H, F/tp]`` (the `parallel/sharding.py` rules — EP on the
  expert axis composes with Megatron TP on the FFN axis);
- every shard computes the (cheap, replicated) router for its token
  shard, then sorts the N*top_k expanded rows so the rows owned by
  THIS shard's experts come first, grouped by local expert — a static
  ``[M]`` sort, no capacity factor and **no token dropping**:
  unowned rows are zero-masked into the trailing group, so outputs are
  exact (a batch-inference engine cannot silently drop tokens — the
  results contract is 1:1, reference README.md:221);
- two grouped GEMMs (+ activation) against the local expert shard,
  combine by the sort's inverse permutation and a weighted sum over
  top_k (``ops/moe.combine``: no scatter), then ONE psum over
  ("expert", "model") merges expert contributions and the TP partial
  sums in a single collective.

FLOP note: the zero-masked tail means each shard still streams M rows
through its GEMMs — EP here buys weight residency and HBM traffic
(1/ep of expert bytes per shard, the decode bottleneck), not FLOP
scaling; FLOPs scale with the ``data`` axis as usual.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .moe import _act, _grouped, _route, combine, held_rows, held_weights


def moe_mlp_ep(
    x: jax.Array,          # [B, T, H]
    router: jax.Array,     # [H, E] (replicated)
    we_gate: jax.Array,    # [E, H, F] — expert/model sharded
    we_up: jax.Array,
    we_down: jax.Array,    # [E, F, H]
    *,
    mesh: Mesh,
    top_k: int,
    activation: str = "silu",
    router_b: Optional[jax.Array] = None,   # [E]
    bias_gate: Optional[jax.Array] = None,  # [E, F]
    bias_up: Optional[jax.Array] = None,    # [E, F]
    bias_down: Optional[jax.Array] = None,  # [E, H]
    route: Optional[dict] = None,  # ``_route``'s keywords (ops/moe.py)
    use_pallas: bool = False,
) -> jax.Array:
    B, T, H = x.shape
    E = router.shape[-1]
    F = we_gate.shape[-1]
    ep = int(mesh.shape.get("expert", 1))
    tp = int(mesh.shape.get("model", 1))
    if E % max(ep, 1):
        raise ValueError(f"expert axis {ep} must divide num_experts {E}")
    if F % max(tp, 1):
        raise ValueError(f"model axis {tp} must divide moe FFN dim {F}")

    # shard tokens over "data" only when divisible; otherwise replicate
    # (correct either way — replication just duplicates router math)
    dp = int(mesh.shape.get("data", 1))
    x_spec = P("data", None, None) if B % max(dp, 1) == 0 else P()

    route = dict(route or {})
    select_bias = route.pop("select_bias", None)

    def body(x_s, router, wg, wu, wd, rb, bg, bu, bd, sb):
        Bl, Tl, _ = x_s.shape
        El = wg.shape[0]
        N = Bl * Tl
        K = top_k
        eidx = jax.lax.axis_index("expert")
        xt = x_s.reshape(N, H)

        top_idx, probs, flat_expert, _, _ = _route(
            xt, router, rb, K, select_bias=sb, **route
        )
        # this shard's share of the rows (ops/moe.py ``held_rows``, the
        # one definition of a held share and of the layer's one sort):
        # its own first, grouped by local expert; the others zero-masked
        # into the trailing group: static shapes, no capacity factor, no
        # dropped tokens
        s_key, order, group_sizes = held_rows(flat_expert, eidx * El, El)
        s_eidx = jnp.minimum(s_key, El - 1)                  # bias index

        lhs = xt[order // K] * (s_key < El)[:, None].astype(xt.dtype)
        g = _grouped(lhs, wg, group_sizes, use_pallas)       # [M, F/tp]
        u = _grouped(lhs, wu, group_sizes, use_pallas)
        if bg is not None:
            g = g + bg[s_eidx].astype(g.dtype)
            u = u + bu[s_eidx].astype(u.dtype)
        a, u = _act(g, u, activation)
        y = _grouped(a * u, wd, group_sizes, use_pallas)     # [M, H]
        if bd is not None:
            # gate/up biases live on the tp-sharded F axis (distinct
            # slices per shard), but bias_down lands on the unsharded H
            # output — every model shard would add it, so pre-divide by
            # the axis size to survive the psum intact
            # axis size via psum(1): works on every jax version (the
            # top-level jax.lax.axis_size helper is newer than some
            # hosts' pins) and folds to a constant under shard_map
            y = y + (
                bd[s_eidx] / jax.lax.psum(1, "model")
            ).astype(y.dtype)
        # back to the tokens as ops/moe.py does it (``combine``, the one
        # definition: the rows gathered by the sort's inverse and summed
        # over top_k in float32, no scatter), this shard's choices alone
        out = combine(y, order, held_weights(top_idx, probs, eidx * El, El))
        # one collective: expert contributions + TP partial sums (the
        # F-axis contraction in the down GEMM is tp-sharded)
        out = jax.lax.psum(out, ("expert", "model"))
        return out.reshape(Bl, Tl, H)

    opt = lambda spec, v: None if v is None else spec  # noqa: E731
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(),
            P("expert", None, "model"),
            P("expert", None, "model"),
            P("expert", "model", None),
            opt(P(), router_b),
            opt(P("expert", "model"), bias_gate),
            opt(P("expert", "model"), bias_up),
            opt(P("expert", None), bias_down),
            opt(P(), select_bias),
        ),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(
        x, router, we_gate, we_up, we_down,
        router_b, bias_gate, bias_up, bias_down, select_bias,
    )
