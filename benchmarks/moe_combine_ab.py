"""What lies ROUND a routed layer's grouped products, alone, on the chip.

Times the expressions of ``ops/moe.py``'s ragged path that are not the
products, as they stand (``sort_rows``, ``rows_by_group``, ``combine``:
one sort, the rows back to their tokens by the sort's inverse and a
reduce over top_k) against what the path ran until PR 44, written out
here (``argsort``, three gathers of scalars, two ``bincount``, a pass
``y * prob`` and a row scatter-add), on the same routing in one process:

    python benchmarks/moe_combine_ab.py --shape mellum2 --tokens 64 2048 4096

A timed call is ``--layers`` layers chained as a model chains them, in
one jitted function, each with its own routing. Two quantities a shape:

- ``round``: sort, group sizes and counts, the gather ``xt[token]`` in
  front of the products, the combine behind them; the products
  themselves are left out (a sorted row goes through as it is, behind an
  ``optimization_barrier``, so ``round`` returns its input);
- ``combine``: the combine alone over given rows.

For a capped share (``joyai`` at a prefill's size: the products run over
the first ``cap`` sorted rows, ``ops/moe._share_row_cap``) ``combine``
compares the two forms that sum those rows: ``old`` scatter-adds the
``cap`` live rows, ``unpermuted`` gathers all ``M`` rows by the inverse,
the rows behind ``cap`` from one zero row, and reduces: the measurement
``ops/moe.combine`` took its capped form from (PERF.md section 6, PR 44).

Shapes (tokens x top_k = expanded rows ``M``):

- ``mellum2``: hidden 2304, 64 experts, top 8 (a decode step of 64 rows,
  one-row prefills of 2,048 and 4,096 tokens);
- ``lfm2``: hidden 2048, 64 experts, top 4 (a step of 256 rows, prefills
  to 1,024);
- ``nemotron``: hidden 2688, 128 experts of which 64 are held, top 6 (a
  step of 256 rows, prefills of 128-1,024);
- ``joyai``: hidden 2048, 256 experts of which 16 are held, top 8 (a
  step of 32 rows, prefills of 2,048 and 4,096: capped).

Prints one JSON line a token count: ms a layer and GB/s of the bytes the
expression has to move (the rows read and written once: not what it
does move). Fails without a TPU unless ``--cpu`` (tiny, to debug).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SHAPES = {
    "mellum2": dict(H=2304, E=64, K=8, held=64),
    "lfm2": dict(H=2048, E=64, K=4, held=64),
    "nemotron": dict(H=2688, E=128, K=6, held=64),
    "joyai": dict(H=2048, E=256, K=8, held=16),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="mellum2")
    ap.add_argument("--tokens", type=int, nargs="+", default=[64, 2048, 4096])
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cpu", action="store_true", help="tiny, to debug")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import moe

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --cpu for a tiny run)")
    shape = dict(SHAPES[args.shape])
    if args.cpu:
        shape.update(H=128, E=16, held=min(shape["held"], 16) // (
            1 if shape["held"] == shape["E"] else 4
        ))
    H, E, K, held = (shape[k] for k in ("H", "E", "K", "held"))
    share = held != E
    L = args.layers
    dtype = jnp.float32 if args.cpu else jnp.bfloat16

    def routing(xt, router):
        top_idx, probs, flat_expert, _, flat_prob = moe._route(
            xt, router, None, K
        )
        return top_idx, probs, flat_expert, flat_prob

    # -- the path until PR 44, written out -------------------------------
    def old_sorted(top_idx, probs, flat_expert, flat_prob):
        N = top_idx.shape[0]
        flat_token = jnp.repeat(jnp.arange(N), K)
        if share:
            key = moe.held_key(flat_expert, 0, held)
            order = jnp.argsort(key, stable=True)
            s_key = key[order]
            counts = jnp.bincount(s_key, length=held + 1)
            sizes = counts[:held].at[held - 1].add(counts[held])
            weight = jnp.where(key < held, flat_prob, 0.0)[order]
            return s_key, flat_token[order], weight, sizes.astype(jnp.int32)
        order = jnp.argsort(flat_expert)
        s_expert = flat_expert[order]
        return (
            s_expert, flat_token[order], flat_prob[order],
            jnp.bincount(s_expert, length=E).astype(jnp.int32),
        )

    def old_combine(y, s_token, s_prob, N):
        y = y * s_prob[: y.shape[0], None].astype(y.dtype)
        return jnp.zeros((N, H), y.dtype).at[s_token[: y.shape[0]]].add(y)

    def old_round(xt, router, rows):
        r = routing(xt, router)
        s_expert, s_token, s_prob, sizes = old_sorted(*r)
        lhs = xt[s_token[:rows]]
        if share:
            lhs = lhs * (s_expert[:rows] < held)[:, None].astype(xt.dtype)
        y = jax.lax.optimization_barrier(lhs)
        counts = jnp.bincount(r[2], length=E).astype(jnp.int32)
        return old_combine(y, s_token, s_prob, xt.shape[0]), sizes, counts

    # -- the path as it stands --------------------------------------------
    def new_sorted(top_idx, probs, flat_expert, flat_prob):
        if share:
            s_expert, order, sizes = moe.held_rows(flat_expert, 0, held)
            return (
                s_expert, order, sizes,
                moe.held_weights(top_idx, probs, 0, held),
            )
        s_expert, order = moe.sort_rows(flat_expert)
        return s_expert, order, moe.rows_by_group(flat_expert, E), probs

    def new_round(xt, router, rows):
        r = routing(xt, router)
        s_expert, order, sizes, weights = new_sorted(*r)
        lhs = xt[order[:rows] // K]
        if share:
            lhs = lhs * (s_expert[:rows] < held)[:, None].astype(xt.dtype)
        y = jax.lax.optimization_barrier(lhs)
        counts = moe.rows_by_group(r[2], E)
        return moe.combine(y, order, weights), sizes, counts

    def chained(round_fn, rows):
        @jax.jit
        def run(xt, routers):
            sizes = counts = 0
            for layer in range(L):
                out, s, c = round_fn(xt, routers[layer], rows)
                # a share returns its own experts' part alone: keep the
                # chain's values from dying out (a whole layer's
                # ``out`` is ``xt`` and this is the identity)
                xt = ((xt + out) * 0.5).astype(xt.dtype)
                sizes, counts = sizes + s, counts + c
            return xt, sizes, counts

        return run

    def combines(fn, rows):
        """``fn`` over ``L`` routings of the same rows, each sorted
        outside the timed call."""
        @jax.jit
        def run(y, sorted_rows):
            out = 0.0
            for layer in range(L):
                at = jax.tree.map(lambda a: a[layer], sorted_rows)
                out = out + fn(y[:rows], *at).astype(jnp.float32)
            return out

        return run

    def best_ms(run, *a):
        jax.block_until_ready(run(*a))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*a))
            times.append(time.perf_counter() - t0)
        return min(times) * 1e3 / L

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    # a router with a skew: the busiest expert several times the mean
    skew = jax.random.normal(keys[2], (1, 1, E), jnp.float32) * 0.5
    routers = (
        jax.random.normal(keys[0], (L, H, E), jnp.float32) / np.sqrt(H) + skew
    ).astype(dtype)
    for N in args.tokens:
        M = N * K
        cap = moe._share_row_cap(M, held, E) if share else None
        rows = cap or M
        xt = jax.random.normal(keys[1], (N, H), jnp.float32).astype(dtype)
        y = jax.random.normal(keys[2], (M, H), jnp.float32).astype(dtype)
        item = xt.dtype.itemsize
        line = dict(
            shape=args.shape, tokens=N, rows=M, cap=cap, layers=L,
            hidden=H, experts=E, held=held, top_k=K,
        )
        if cap is not None:
            owned = [
                int(jnp.sum(routing(xt, routers[layer])[2] < held))
                for layer in range(L)
            ]
            line["rows_owned_max"] = max(owned)
            if max(owned) > cap:
                line["note"] = "a layer's own rows pass the cap"
        # the bytes an expression has to move, once each
        round_bytes = (N + rows + rows + N) * H * item
        combine_bytes = (rows + N) * H * item
        # the two expressions on ONE layer's routing (chained, a last
        # bit of a row moves the next layer's routing)
        want, sizes0, counts0 = jax.jit(old_round, static_argnums=2)(
            xt, routers[0], rows
        )
        got, sizes1, counts1 = jax.jit(new_round, static_argnums=2)(
            xt, routers[0], rows
        )
        line["group_sizes_equal"] = bool(jnp.array_equal(sizes0, sizes1))
        line["counts_equal"] = bool(jnp.array_equal(counts0, counts1))
        line["round_max_diff_over_max"] = float(
            jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()
            / jnp.abs(want.astype(jnp.float32)).max()
        )
        per_layer = [routing(xt, routers[layer]) for layer in range(L)]
        stack = lambda rows_: jax.tree.map(  # noqa: E731
            lambda *a: jnp.stack(a), *rows_
        )
        forms = {
            # until PR 44 (a capped share: the scatter of its live rows)
            "old": (
                lambda y, s_token, s_prob: old_combine(y, s_token, s_prob, N),
                stack([old_sorted(*r)[1:3] for r in per_layer]),
            ),
            # as it stands (a capped share: the rest from a zero row)
            "unpermuted": (
                moe.combine,  # (y, order, weights)
                stack([
                    (order, weights) for _, order, _, weights in
                    (new_sorted(*r) for r in per_layer)
                ]),
            ),
        }
        for name, fn in (("old", old_round), ("new", new_round)):
            ms = best_ms(chained(fn, rows), xt, routers)
            line[f"round_{name}"] = dict(
                ms_a_layer=ms, gb_s=round_bytes / ms / 1e6
            )
        ref = None
        for name, (fn, sorted_rows) in forms.items():
            run = combines(fn, rows)
            out = np.asarray(run(y, sorted_rows))
            ref = out if ref is None else ref
            ms = best_ms(run, y, sorted_rows)
            line[f"combine_{name}"] = dict(
                ms_a_layer=ms, gb_s=combine_bytes / ms / 1e6,
                max_diff_over_max=float(
                    np.abs(out - ref).max() / np.abs(ref).max()
                ),
            )
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
