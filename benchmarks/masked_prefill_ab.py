"""A selecting prefill's attention alone, on the chip, at GLM-5's shapes.

Times ``ops/sparse_attention.masked_attention`` on ONE layer's operands
(a chunk with no past in the expanded form: 64 heads with K and V of
their own, 256 wide; an indexer of 32 heads of 128 keeping 2,048) with
its attention taken both ways, in one process on the same operands: the
XLA body (``use_pallas`` off: a block of queries at a time, float32
scores through HBM) and the flash body under the selection's mask
(``use_pallas``), and beside them ``flash_prefill`` alone under the same
mask at each block side, so that the selection's own cost is the
difference:

    python benchmarks/masked_prefill_ab.py --tokens 8192 16384

Prints one JSON line a chunk length: ms a call, the needed attention
FLOPs (2 x (Dq + Dv) x heads x min(t + 1, topk) a query) over each time,
and the flash path's largest difference from the XLA body over its
largest value. Fails without a TPU (``--cpu``: a tiny interpreted run).
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+", default=[8192])
    ap.add_argument("--blocks", type=int, nargs="+", default=[1024, 512, 256])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cpu", action="store_true", help="tiny, interpreted")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import attention, pallas_flash
    from sutro_tpu.ops.sparse_attention import Indexer, masked_attention

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --cpu for a tiny interpreted run)")
    NH, D, NHi, Di, topk, dtype = 64, 256, 32, 128, 2048, jnp.bfloat16
    if args.cpu:
        NH, D, NHi, Di, topk, dtype = 4, 128, 3, 24, 8, jnp.float32
        pallas_flash.flash_prefill = functools.partial(
            pallas_flash.flash_prefill, interpret=True
        )
    scale = D ** -0.5

    def timed(fn, *a):
        fn(*a).block_until_ready()
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            fn(*a).block_until_ready()
            times.append(time.perf_counter() - t0)
        return min(times)

    for T in args.tokens:
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        q, k, v = (jax.random.normal(x, (1, T, NH, D), dtype) for x in keys[:3])
        index = (
            jax.random.normal(keys[3], (1, T, NHi, Di), dtype),
            jax.random.normal(keys[4], (1, T, NHi), jnp.float32),
            jax.random.normal(keys[5], (1, T, Di), dtype),
        )
        kw = dict(
            positions=jnp.arange(T, dtype=jnp.int32)[None],
            valid_len=jnp.asarray([T - T // 7], jnp.int32), scale=scale,
        )
        n = np.minimum(np.arange(T) + 1, topk).sum()
        flops = 2.0 * (D + D) * NH * float(n)
        line = dict(tokens=T, heads=NH, topk=topk, needed_tflop=flops / 1e12)

        def attend(q, k, v, index, **how):
            return masked_attention(
                q, k, v, Indexer(*index, topk=topk), **how, **kw)

        want, keep = jax.jit(functools.partial(
            attend, return_mask=True))(q, k, v, index)
        keep = keep.astype(jnp.int8)
        for label, flag in (("xla", False), ("flash", True)):
            fn = jax.jit(functools.partial(attend, use_pallas=flag))
            best = timed(fn, q, k, v, index)
            line[label] = dict(ms=best * 1e3, needed_tflop_s=flops / best / 1e12)
            if flag:
                got = np.asarray(fn(q, k, v, index), np.float32)
                ref = np.asarray(want, np.float32)
                valid = int(kw["valid_len"][0])
                line[label]["max_diff_over_max"] = float(
                    np.abs(got[:, :valid] - ref[:, :valid]).max()
                    / np.abs(ref[:, :valid]).max()
                )
                line[label]["finite"] = bool(np.isfinite(got).all())
        for block in args.blocks:
            if T % block:
                continue
            fn = jax.jit(functools.partial(
                attention.latent_flash, scale=scale, block=block))
            try:
                best = timed(lambda *a: fn(*a[:3], keep=a[3]), q, k, v, keep)
                line[f"kernel_{block}"] = dict(
                    ms=best * 1e3, needed_tflop_s=flops / best / 1e12)
                best = timed(fn, q, k, v)
                line[f"kernel_{block}_dense"] = dict(ms=best * 1e3)
            except Exception as e:  # noqa: BLE001 - a block the chip refuses
                line[f"kernel_{block}"] = dict(refused=str(e)[-300:])
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
