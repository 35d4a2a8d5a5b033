"""The routed experts' grouped product alone, on the chip, at a cell's shapes.

Times ``ops/pallas_gmm.grouped_matmul`` against ``jax.lax.ragged_dot`` over
the flat expert stack of a routed model (``ops/moe.py:_grouped`` with
``layer=``), both in one process on the same operands:

    python benchmarks/grouped_matmul_ab.py --shape mellum2 --rows 512 16384 32768

A timed call is what one forward runs: for each of ``L`` routed layers the
gate, up and down products (``3 L`` grouped products, 24 at ``L = 8``),
chained as the FFN chains them, in one jitted function. Shapes:

- ``mellum2``: 8 layers x 64 experts, hidden 2304, expert width 896,
  top-8 (``mellum2-12b-a2.5b-l8``: rows = tokens x 8; a decode step of
  64 rows is 512, the one-row prefills are 16,384 and 32,768);
- ``lfm2``: 6 routed layers x 64 experts, hidden 2048, width 1536, top-4
  (``lfm2-24b-a2b-l10``: a decode step of 128 rows is 512).

Rows go to experts as a seeded multinomial with a skew (the busiest expert
near five times the mean, what the cells' ``moe_expert_rows_max_over_mean``
reads). ``--tiles tm,ts[,tn]`` times the kernel at other tiles than its
policy picks (``pallas_gmm._tiles``), for choosing that policy. Prints one
JSON line a row count: ms a product, GB/s of experts touched, TFLOP/s of
rows computed, and the largest difference between the two results over
the largest value. Fails without a TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SHAPES = {
    "mellum2": dict(L=8, E=64, H=2304, F=896),
    "lfm2": dict(L=6, E=64, H=2048, F=1536),
}


def group_sizes(rng, L: int, E: int, M: int):
    """``[L, E]`` rows an expert: a multinomial over skewed shares."""
    import numpy as np

    share = rng.dirichlet(np.full((E,), 1.2), size=L)
    return np.stack([rng.multinomial(M, p) for p in share]).astype(np.int32)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="mellum2")
    ap.add_argument("--rows", type=int, nargs="+", default=[512, 16384, 32768])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--tiles", default="")
    ap.add_argument("--cpu", action="store_true", help="tiny, interpreted")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import pallas_gmm

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --cpu for a tiny interpreted run)")
    shape = dict(SHAPES[args.shape])
    if args.cpu:
        shape.update(L=2, E=8, H=128, F=128)
    L, E, H, F = (shape[k] for k in "LEHF")
    if args.tiles:
        forced = tuple(int(t) for t in args.tiles.split(","))
        policy = pallas_gmm._tiles
        pallas_gmm._tiles = lambda M, K, N, b: (
            forced + policy(M, K, N, b)[len(forced):]
        )
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    stacks = [
        (jax.random.normal(k, (L * E,) + s, jnp.float32) * 0.02).astype(dtype)
        for k, s in zip(keys, [(H, F), (H, F), (F, H)])
    ]
    rng = np.random.default_rng(args.seed)

    def one_product(kernel: bool, lhs, rhs, gs, layer):
        if kernel:
            return pallas_gmm.grouped_matmul(
                lhs, rhs, gs, layer, interpret=args.cpu
            )
        flat = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), jnp.int32), gs, (layer * E,)
        )
        return jax.lax.ragged_dot(lhs, rhs, flat)

    def forward(kernel: bool):
        product = functools.partial(one_product, kernel)

        @jax.jit
        def run(x, wg, wu, wd, sizes):
            for layer in range(L):
                at = jnp.int32(layer)
                g = product(x, wg, sizes[layer], at)
                u = product(x, wu, sizes[layer], at)
                x = product(jax.nn.silu(g) * u, wd, sizes[layer], at)
            return x

        return run

    for M in args.rows:
        sizes = group_sizes(rng, L, E, M)
        x = jax.random.normal(keys[3], (M, H), jnp.float32).astype(dtype)
        a = (x, *stacks, jnp.asarray(sizes))
        line = dict(
            shape=args.shape, rows=M, products=3 * L,
            tiles=pallas_gmm._tiles(M, H, F, x.dtype.itemsize),
            rows_max_over_mean=float(sizes.max() / sizes.mean()),
            experts_touched=int((sizes > 0).sum()),
        )
        touched = int((sizes > 0).sum()) * 3 * H * F * x.dtype.itemsize
        for name in ("ragged_dot", "kernel"):
            run = forward(name == "kernel")
            run(*a).block_until_ready()
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                run(*a).block_until_ready()
                times.append(time.perf_counter() - t0)
            best = min(times)
            line[name] = dict(
                ms_a_product=best * 1e3 / (3 * L),
                experts_gb_s=touched / best / 1e9,
                tflop_s=2 * M * H * F * 3 * L / best / 1e12,
            )
        # the chained values shrink layer by layer: compare ONE product,
        # the last layer's (the stack indexed furthest from its start)
        want, got = (
            np.asarray(jax.jit(functools.partial(one_product, k))(
                x, stacks[0], a[-1][L - 1], jnp.int32(L - 1)
            ), np.float32)
            for k in (False, True)
        )
        line["max_diff_over_max"] = float(
            np.abs(got - want).max() / np.abs(want).max()
        )
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
