"""A Mamba-2 decode step's read of its committed state alone, on the chip,
at a cell's shapes.

Times ``models/transformer.ssd_pending`` over the stacked slot pool with
its state read taken three ways, in one process on the same operands: the
XLA expression (``use_pallas`` off) and ``ops/pallas_ssm.ssm_state_read``
in each of its forms (``mxu``: C split into three bf16 terms against the
bf16 block; ``vpu``: the block converted to float32, multiplied and
reduced over the sublanes):

    python benchmarks/ssm_state_read_ab.py --shape nemotron
    python benchmarks/ssm_state_read_ab.py --shape granite

A timed call is what one decode step runs: every mamba layer's read in
turn, the layer a traced index into the pool inside ``lax.scan`` (as the
model's walk indexes it), ``--calls`` reads at least. A chunk of
``--tokens`` 1 has no uncommitted token before it, so what is timed
beside the read is a ``[B, 1, 1]`` product. Shapes:

- ``nemotron``: 6 layers x 257 slots x 128 x 4,096 bf16, 8 groups, batch
  256 (``nemotron-3-nano-30b-a3b-l14.generate-long-output-jobs``);
- ``granite``: 36 layers x 129 slots, 1 group, batch 128
  (``granite-4.0-h-micro.generate-short-jobs``).

Rows hold slots in a seeded random order. Prints one JSON line a shape:
ms a read, GB/s of the rows' state (batch x N x I x itemsize; the XLA
expression reads the pool's one spare slot too), and each kernel form's
largest difference from the XLA expression over its largest value. Fails
without a TPU (``--cpu``: a tiny interpreted run).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SHAPES = {
    "nemotron": dict(L=6, NS=257, B=256, G=8),
    "granite": dict(L=36, NS=129, B=128, G=1),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), nargs="+",
                    default=sorted(SHAPES))
    ap.add_argument("--batch", type=int, default=0, help="rows (0: the cell's)")
    ap.add_argument("--tokens", type=int, default=1)
    ap.add_argument("--calls", type=int, default=36)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cpu", action="store_true", help="tiny, interpreted")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS
    from sutro_tpu.ops import pallas_ssm

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --cpu for a tiny interpreted run)")
    kernel = pallas_ssm.ssm_state_read
    dtype = jnp.bfloat16
    T = args.tokens
    for name in args.shape:
        shape = dict(SHAPES[name], N=128, I=4096)
        if args.cpu:
            shape.update(L=2, NS=5, B=4, I=128 * shape["G"])
        L, NS, B, G, N, I = (shape[k] for k in ("L", "NS", "B", "G", "N", "I"))
        B = args.batch or B
        cfg = dataclasses.replace(
            MODEL_CONFIGS["tiny-granite"], mamba_heads=I // 64,
            mamba_head_dim=64, mamba_state=N, mamba_groups=G,
        )
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        pool = jax.random.normal(keys[0], (L, NS, N, I), dtype)
        rng = np.random.default_rng(args.seed)
        slots = jnp.asarray(
            1 + rng.permutation(NS - 1)[np.arange(B) % (NS - 1)], jnp.int32
        )
        x, dt, Bm, Cq = (
            jax.random.normal(k, (B, T, w), jnp.float32)
            for k, w in zip(
                keys[1:], (I, cfg.mamba_heads, G * N, G * N)
            )
        )
        dt = jax.nn.softplus(dt)
        reps = -(-args.calls // L)

        def forward(form):
            pallas_ssm.ssm_state_read = functools.partial(
                kernel, form=form, interpret=args.cpu
            )

            def read(layer, pool, slots, x, dt, Bm, Cq):
                return transformer.ssd_pending(
                    cfg, pool, layer, slots, jnp.zeros((B,), bool),
                    x, dt, -dt, Bm, Cq, 0, use_pallas=form is not None,
                )

            @jax.jit
            def run(*a):
                layers = jnp.arange(reps * L, dtype=jnp.int32) % L
                # a sum a call keeps every read live and the result small
                return jax.lax.scan(
                    lambda acc, l: (acc + read(l, *a), None),
                    jnp.zeros((B, T, I), jnp.float32), layers,
                )[0]

            return run, jax.jit(lambda *a: read(jnp.int32(L - 1), *a))

        a = (pool, slots, x, dt, Bm, Cq)
        line = dict(
            shape=name, layers=L, slots=NS, batch=B, groups=G, tokens=T,
            reads_a_call=reps * L,
            state_mb_a_read=B * N * I * pool.dtype.itemsize / 1e6,
        )
        want = None
        for label, form in (("xla", None), ("mxu", "mxu"), ("vpu", "vpu")):
            run, one = forward(form)
            run(*a).block_until_ready()
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                run(*a).block_until_ready()
                times.append(time.perf_counter() - t0)
            best = min(times) / (reps * L)
            line[label] = dict(
                ms_a_read=best * 1e3,
                state_gb_s=B * N * I * pool.dtype.itemsize / best / 1e9,
            )
            got = np.asarray(one(*a), np.float32)
            if want is None:
                want = got
            else:
                line[label]["max_diff_over_max"] = float(
                    np.abs(got - want).max() / np.abs(want).max()
                )
        pallas_ssm.ssm_state_read = kernel
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
