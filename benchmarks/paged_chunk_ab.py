"""A chunk of several tokens over a paged past, alone, on the chip: the
kernel that reads the row's pages where they lie
(``ops/pallas_chunk.paged_chunk_attention``) against the gather it
replaces (``ops/attention.chunk_attention`` with the kernels off: the
row's whole table gathered, float32 products, the scores through HBM),
at the classify cell's two shapes:

    python benchmarks/paged_chunk_ab.py --shape verify
    python benchmarks/paged_chunk_ab.py --shape chunk

- ``verify``: ``_verify_cand_jit``'s ``[64, 17]``: every row holds the
  job's shared prefix (4 pages), a review and what it has generated
  (``benchmarks/paged_kernel_ab.py``'s classify tables), and verifies 1
  to 17 forced tokens;
- ``chunk`` / ``chunk512``: ``_prefill_chunk_jit``'s ``[8, 256]`` /
  ``[8, 512]``: the suffix of 8 rows over the shared prefix's pages,
  each more than half its bucket long.

A timed call is a layer stack's worth of the attention: 36 calls in one
jitted loop over the layers of an 8-layer stacked pool (qwen3-4b's
heads: 32 over 8 of 128, pages of 64, tables of 16, 579 pages). Prints
one JSON line: ms a stack by path, the GB/s of the pages the rows NEED
(K and V of ``ceil(past_len / 64)`` pages a row), and the largest
difference between the paths' outputs at the valid queries. ``--cpu``
is a tiny interpreted run of the same control flow; without it the
script fails where there is no TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SHAPES = {
    "verify": dict(B=64, T=17),
    "chunk": dict(B=8, T=256),
    "chunk512": dict(B=8, T=512),
}
NH, KVH, DH, PS, MP, NP, LAYERS, CALLS = 32, 8, 128, 64, 16, 579, 8, 36
SHARED = 4      # pages of the job's shared prefix


def rows(rng, shape: str, B: int, T: int):
    """``(past_len [B], valid_len [B], table [B, MP])`` of a shape."""
    import numpy as np

    table = np.zeros((B, MP), np.int32)
    table[:, :SHARED] = np.arange(1, 1 + SHARED)
    if shape == "verify":
        own = rng.integers(80, 401, B) + 24 + rng.integers(0, 97, B)
        past = SHARED * PS + own
        valid = rng.integers(1, T + 1, B)
    else:
        past = np.full((B,), SHARED * PS)
        valid = rng.integers(T // 2 + 1, T + 1, B)
    pages = np.minimum(-(-(past + T) // PS), MP)
    past = np.minimum(past, pages * PS - T)
    nxt = 1 + SHARED
    for b in range(B):
        own = int(pages[b]) - SHARED
        table[b, SHARED:SHARED + own] = np.arange(nxt, nxt + own)
        nxt += own
    assert nxt <= NP, (nxt, NP)
    return past.astype(np.int32), valid.astype(np.int32), table


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="verify")
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import lowering
    from sutro_tpu.ops.attention import chunk_attention
    from sutro_tpu.ops.pallas_chunk import chunk_tiles, paged_chunk_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    B, T = SHAPES[args.shape]["B"], SHAPES[args.shape]["T"]
    nh, kvh, layers, calls, npages = NH, KVH, LAYERS, CALLS, NP
    if args.cpu:
        B, nh, kvh, layers, calls, npages = min(B, 4), 4, 1, 2, 2, 80
        T = min(T, 32)
    KD = kvh * DH
    rng = np.random.default_rng(args.seed)
    past, valid, table = rows(rng, args.shape, B, T)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    bf = jnp.bfloat16
    kp = jax.random.normal(ks[0], (layers, npages, PS, KD), bf)
    vp = jax.random.normal(ks[1], (layers, npages, PS, KD), bf)
    q = jax.random.normal(ks[2], (B, T, nh, DH), bf)
    kc = jax.random.normal(ks[3], (B, T, kvh, DH), bf)
    vc = jax.random.normal(ks[4], (B, T, kvh, DH), bf)
    table_d, past_d, valid_d = map(jnp.asarray, (table, past, valid))
    zero = jnp.asarray(0, jnp.int32)

    # every array is an ARGUMENT of the jitted programs: a closed-over
    # pool would be compiled in as a 600 MB constant
    arrays = (q, kc, vc, kp, vp, table_d, past_d, valid_d)

    def kernel(layer, q, kc, vc, kp, vp, table, past, valid):
        return paged_chunk_attention(
            q, kc, vc, kp, vp, layer, table, past, valid, zero,
            interpret=args.cpu,
        )

    def gather(layer, q, kc, vc, kp, vp, table, past, valid):
        return chunk_attention(
            q, kc, vc,
            positions=past[:, None] + jnp.arange(T, dtype=jnp.int32)[None],
            valid_len=valid, past_k_pages=kp, past_v_pages=vp, layer=layer,
            page_table=table, past_len=past, window=zero,
        )

    def stack(one):
        @jax.jit
        def run(*arrays):
            def body(i, acc):
                return acc + one(i % layers, *arrays).astype(jnp.float32)

            return jax.lax.fori_loop(
                0, calls, body, jnp.zeros((B, T, nh, DH), jnp.float32)
            )

        return run

    live = np.arange(T)[None] < valid[:, None]
    needed = float((-(-past // PS)).sum()) * 2 * PS * KD * 2
    out = {
        "shape": args.shape, "B": B, "T": T, "seed": args.seed,
        "device": dev.device_kind,
        "tiles": chunk_tiles(T, nh, kvh, DH, PS, pool_bytes=2, io_bytes=2),
        "mean_past": float(past.mean()), "mean_valid": float(valid.mean()),
        "kv_mb_needed_a_call": round(needed / 1e6, 2),
        "kv_mb_gathered_a_call": round(B * MP * 2 * PS * KD * 2 / 1e6, 2),
    }
    outs = {}
    for name, one in (("kernel", kernel), ("gather", gather)):
        outs[name] = np.asarray(jax.jit(one)(zero, *arrays), np.float32)
        run = stack(one)
        run(*arrays).block_until_ready()
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            run(*arrays).block_until_ready()
            times.append(time.perf_counter() - t0)
        med = float(np.median(times))
        out[name] = {
            "stack_ms": round(med * 1e3, 3),
            "min_stack_ms": round(min(times) * 1e3, 3),
            "call_us": round(med / calls * 1e6, 1),
            "needed_gb_s": round(needed * calls / med / 1e9, 1),
        }
    out["max_abs_diff_valid"] = float(
        np.abs(outs["kernel"] - outs["gather"])[live].max()
    )
    out["paged_chunk"] = lowering.paged_chunk_counts()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
