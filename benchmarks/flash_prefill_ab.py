"""The flash prefill body of a GQA layer, alone, on the chip: what the
body was (every operand up-cast to float32, tiles of 128 x 128, the
running statistics read back as one value a row, the whole causal half
walked under a dynamic window, the bucket's padding computed) beside
what ``ops/pallas_flash.flash_prefill`` runs now, and one row a PART of
the change so that each is priced alone:

    python benchmarks/flash_prefill_ab.py --shape laguna-window --valid 5200
    python benchmarks/flash_prefill_ab.py --shape mellum2-full --tiles 256x256,512x256

- ``before``: float32 operands, 128 x 128, the causal half, no
  ``valid_len`` (the body's own keywords for the builder, ``tiles`` and
  ``before``, give it back);
- ``operands``: ``before`` with the operands as they are (bfloat16);
- ``tile``: ``before`` at the tile ``gqa_tiles`` chooses;
- ``stats``: ``before`` with the running ``(m, l)`` lane-replicated;
- ``walk``: ``before`` over the tiles the row needs alone (the static
  window's blocks, nothing past ``--valid``);
- ``now``: all four; ``--tiles`` adds a row a tile under ``now``'s
  operands, statistics and walk.

A timed call is ``--calls`` layers' worth of the attention in one jitted
loop. Prints one JSON line: ms a call by row, the grid steps a call
runs and skips (by the walk's own arithmetic), the share of the bf16
peak that the NEEDED products (the valid queries against the keys their
mask admits) come to, and the largest difference from ``before`` at the
valid queries. ``--cpu`` is a tiny interpreted run of the same control
flow; without it the script fails where there is no TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

#: T, query heads, K/V heads, the layer's window (None: a scan's layer,
#: whose window is a runtime scalar of 0)
SHAPES = {
    "laguna-window": (8192, 72, 8, 512),
    "laguna-full": (8192, 48, 8, 0),
    "mellum2-window": (4096, 32, 4, 1024),
    "mellum2-full": (4096, 32, 4, 0),
    "4b": (1024, 32, 8, None),
    "nemotron": (2048, 32, 2, None),
}
DH = 128
BF16_PEAK = 197e12          # one v5e chip (perfbench/peaks.py)


def steps(T, BQ, BK, window, valid, walked: bool):
    """``(grid steps, steps that run)`` a K/V head of one row. ``walked``:
    the static window bounds the key axis and ``valid`` the tiles."""
    from sutro_tpu.ops.pallas_flash import key_steps

    nQ, run = T // BQ, 0
    span = key_steps(T, BQ, BK, window if walked and window and window < T else 0)
    for qb in range(nQ):
        q0 = qb * BQ
        if walked and q0 >= valid:
            continue
        for kb in range(T // BK):
            k0 = kb * BK
            if k0 > q0 + BQ - 1 or (walked and k0 >= valid):
                continue
            if window and k0 + BK - 1 <= q0 - window:
                continue
            run += 1
    return nQ * span, run


def needed_flops(valid, NH, window):
    """Both products of the valid queries against the keys they see."""
    pairs = sum(min(t + 1, window or t + 1) for t in range(valid))
    return 4 * pairs * DH * NH


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="laguna-window")
    ap.add_argument("--valid", type=int, default=0, help="the row's real tokens")
    ap.add_argument("--tiles", default="", help="BQxBK[,BQxBK...] more rows")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import lowering
    from sutro_tpu.ops.pallas_flash import flash_prefill, gqa_tiles

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    T, NH, KVH, window = SHAPES[args.shape]
    calls = args.calls
    if args.cpu:
        T, NH, KVH, calls = 512, NH // KVH, 1, 2
        window = window and 192
    valid = args.valid or T
    G = NH // KVH
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (calls, 1, T, NH, DH), bf)
    k = jax.random.normal(ks[1], (calls, 1, T, KVH, DH), bf)
    v = jax.random.normal(ks[2], (calls, 1, T, KVH, DH), bf)
    vl = jnp.asarray([valid], jnp.int32)
    win = jnp.asarray(window or 0, jnp.int32)
    chosen = gqa_tiles(T, G, DH, DH)
    small = (128, 128)

    def body(tiles, before, walked):
        # what the body was reads the window as it runs and knows no
        # row's end; the walk takes the window before tracing
        kw = dict(tiles=tiles, before=before, interpret=args.cpu)
        if walked:
            kw.update(valid_len=vl, live_window=window or 0)
            if window is None:
                kw.update(window=win)
        else:
            kw.update(window=win)
        return lambda q, k, v: flash_prefill(q, k, v, **kw)

    both = ("operands", "stats")
    rows = {
        "before": (small, both, False),
        "operands": (small, ("stats",), False),
        "tile": (chosen, both, False),
        "stats": (small, ("operands",), False),
        "walk": (small, both, True),
        "now": (chosen, (), True),
    }
    for t in filter(None, args.tiles.split(",")):
        rows[f"now@{t}"] = (tuple(int(x) for x in t.split("x")), (), True)

    def stack(one):
        @jax.jit
        def run(q, k, v):
            def step(i, acc):
                return acc + one(q[i], k[i], v[i]).astype(jnp.float32)

            return jax.lax.fori_loop(
                0, calls, step, jnp.zeros((1, T, NH, DH), jnp.float32)
            )

        return run

    flops = needed_flops(valid, NH, window)
    out = {
        "shape": args.shape, "T": T, "heads": [NH, KVH], "window": window,
        "valid": valid, "seed": args.seed, "device": dev.device_kind,
        "chosen_tile": list(chosen),
        "needed_gflop_a_call": round(flops / 1e9, 2),
    }
    first = None
    for name, (tiles, before, walked) in rows.items():
        one = body(tiles, before, walked)
        try:
            got = np.asarray(jax.jit(one)(q[0], k[0], v[0]), np.float32)
            run = stack(one)
            run(q, k, v).block_until_ready()
        except Exception as e:  # noqa: BLE001 - a tile the compiler refuses
            out[name] = {"tile": list(tiles), "refused": str(e)[:300]}
            continue
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            run(q, k, v).block_until_ready()
            times.append(time.perf_counter() - t0)
        med = float(np.median(times)) / calls
        grid, ran = steps(T, *tiles, window, valid, walked)
        first = got if first is None else first
        out[name] = {
            "tile": list(tiles),
            "call_ms": round(med * 1e3, 3),
            "min_call_ms": round(min(times) / calls * 1e3, 3),
            "steps_a_kv_head": grid, "steps_run": ran,
            "steps_skipped": grid - ran,
            "needed_share_of_bf16_peak": round(flops / med / BF16_PEAK, 4),
            "max_abs_diff_valid": float(
                np.abs(got - first)[0, :valid].max()
            ),
        }
        if walked and valid < T:
            tail = got[0, -(-valid // tiles[0]) * tiles[0]:]
            out[name]["padding_tiles_zero"] = not tail.any()
    out["flash_prefill"] = lowering.flash_prefill_counts()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
