"""The paged decode kernel alone, on the chip, at a cell's shapes.

One process times ``paged_decode_attention`` of ONE tree (``--tree``: this
checkout, or a parent commit unpacked under ``.stage/``) so that two
trees can be compared in one chip call, run after run:

    python benchmarks/paged_kernel_ab.py --tree .stage/parent --shape 4b
    python benchmarks/paged_kernel_ab.py --tree . --shape 4b

A timed call is a decode step's worth of the kernel: 36 calls in one
jitted loop over the layers of an 8-layer stacked pool, with a fused
window buffer of 8 slots as the generate cells run it. Shapes:

- ``4b``: qwen3-4b on one chip, ``[B=64, NH=32, KD=1024]``, 579 pages;
- ``tp4``: one shard of qwen3-8b under tp=4, ``[B=128, NH=8, KD=256]``,
  2,049 pages (a shard is what one chip runs: no mesh needed);
- ``classify``: the 4b shape with the classify cell's tables, every row's
  table starting with the same shared prefix pages.

Row lengths follow ``perfbench/traffic/generate-jobs.json`` (prompt chars
lognormal 48-480 with every eighth 520-672, +19 tokens of template, a
cap from the cycle 192-320, each row somewhere along its output); 94 %
of the slots are live. Tables are one ascending run a row, as the
allocator's first fit gives them; ``--scatter`` shuffles every row's
pages (what sends a parent's whole batch to its per-page walk).

A tree whose kernel still takes ``kv_chunk`` is timed on both of its
branches (its chunk, and 1). A tree whose kernel takes a block of rows a
grid step says which it built (``rows_per_step``; a tree from before
reads 1) and ``--rows`` names one instead. Beside the GB/s a run prints
the microseconds a live row (``row_us``). Prints one JSON line; fails
without a TPU.
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
from pathlib import Path

SHAPES = {
    "4b": dict(B=64, NH=32, KVH=8, NP=579),
    "tp4": dict(B=128, NH=8, KVH=2, NP=2049),
    "classify": dict(B=64, NH=32, KVH=8, NP=579),
}
DH, PS, MP, LAYERS, CALLS, WINDOW = 128, 64, 16, 8, 36, 8


def row_lengths(rng, shape: str, B: int):
    """``(past_len [B], worst-case pages [B], shared prefix pages)``."""
    import numpy as np

    live = int(round(0.94 * B))
    past = np.zeros((B,), np.int64)
    total = np.zeros((B,), np.int64)
    if shape == "classify":
        # the template's system prompt and schema shell: 4 shared pages;
        # a review of 80-400 chars and up to 96 new tokens of its own
        shared = 4
        own = rng.integers(80, 401, B) + 24
        done = rng.integers(0, 97, B)
        past[:] = shared * PS + own + done
        total[:] = shared * PS + own + 96
        return past, -(-total // PS), shared
    cycle = [192, 320, 256, 224, 288, 256]
    for b in range(live):
        chars = (
            rng.integers(520, 673) if b % 8 == 7
            else int(np.clip(rng.lognormal(np.log(160), 0.55), 48, 480))
        )
        cap = cycle[b % len(cycle)]
        past[b] = chars + 19 + rng.integers(0, cap + 1)
        total[b] = min(chars + 19 + cap, MP * PS)
    past = np.minimum(past, MP * PS - 1)
    return past, -(-total // PS), 0


def tables(rng, past, pages, shared: int, NP: int, scatter: bool):
    import numpy as np

    B = len(past)
    table = np.zeros((B, MP), np.int32)
    nxt = 1 + shared
    for b in range(B):
        if pages[b] == 0:
            continue
        own = int(pages[b]) - shared
        table[b, :shared] = np.arange(1, 1 + shared)
        run = np.arange(nxt, nxt + own)
        if scatter:
            rng.shuffle(run)
        table[b, shared : shared + own] = run
        nxt += own
    assert nxt <= NP, (nxt, NP)
    return table


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=".")
    ap.add_argument("--shape", choices=sorted(SHAPES), default="4b")
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--scatter", action="store_true")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument(
        "--past", type=int, default=None,
        help="every live row holds this many tokens (0: no page at all),"
        " to separate a row's fixed cost from a page's and a group's",
    )
    ap.add_argument(
        "--rows", type=int, default=None,
        help="rows a grid step, where the tree's kernel takes a block of "
        "them (default: what the tree chooses for the shape)",
    )
    ap.add_argument(
        "--set", action="append", default=[], metavar="NAME=INT",
        help="set a module constant of the tree's ops/pallas_paged.py "
        "for this run (RING_BYTES, RING_MAX_PAGES, GROUP_TOKENS)",
    )
    args = ap.parse_args()
    sys.path.insert(0, str(Path(args.tree).resolve()))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import pallas_paged

    for item in args.set:
        name, value = item.split("=")
        if not hasattr(pallas_paged, name):
            raise SystemExit(f"{args.tree} has no pallas_paged.{name}")
        setattr(pallas_paged, name, int(value))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    sh = SHAPES[args.shape]
    B, NH, KVH, NP = sh["B"], sh["NH"], sh["KVH"], sh["NP"]
    KD = KVH * DH
    rng = np.random.default_rng(args.seed)
    past, pages, shared = row_lengths(rng, args.shape, B)
    if args.past is not None:
        past = np.where(past > 0, args.past, 0)
        pages = np.where(past > 0, max(-(-args.past // PS), 1), 0)
        shared = 0
    table = tables(rng, past, pages, shared, NP, args.scatter)

    params = inspect.signature(pallas_paged.paged_decode_attention).parameters
    takes_chunk = "kv_chunk" in params
    if args.rows is not None and "rows" not in params:
        raise SystemExit(f"{args.tree}'s kernel takes one row a grid step")
    rows_kw = {} if args.rows is None else {"rows": args.rows}
    chunks = [None]
    if takes_chunk:
        ch = pallas_paged.chunk_pages_for(PS, MP, kv_heads=KVH, head_dim=DH)
        chunks = [1] if (args.scatter or shared) else [ch, 1]
        NP += ch - 1  # the parent's slack pages at the pool's end

    key = jax.random.PRNGKey(args.seed)
    ks = jax.random.split(key, 8)
    kp = jax.random.normal(ks[0], (LAYERS, NP, PS, KD), jnp.bfloat16)
    vp = jax.random.normal(ks[1], (LAYERS, NP, PS, KD), jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, NH, DH), jnp.bfloat16)
    kc = jax.random.normal(ks[3], (B, KVH, DH), jnp.bfloat16)
    vc = jax.random.normal(ks[4], (B, KVH, DH), jnp.bfloat16)
    wk = jax.random.normal(ks[5], (B, WINDOW, KD), jnp.bfloat16)
    wv = jax.random.normal(ks[6], (B, WINDOW, KD), jnp.bfloat16)
    table_d = jnp.asarray(table)
    past_d = jnp.asarray(past, jnp.int32)
    zero = jnp.asarray(0, jnp.int32)

    def step_fn(kv_chunk):
        kw = dict(rows_kw)
        if kv_chunk is not None:
            kw["kv_chunk"] = kv_chunk

        @jax.jit
        def step(q, kp, vp, table, past, kc, vc, wk, wv):
            def body(i, acc):
                out = pallas_paged.paged_decode_attention(
                    q, kp, vp, i % LAYERS, table, past, kc, vc, zero,
                    None, wk, wv, jnp.asarray(4, jnp.int32), **kw,
                )
                return acc + out.astype(jnp.float32)

            return jax.lax.fori_loop(
                0, CALLS, body, jnp.zeros((B, NH, DH), jnp.float32)
            )

        return step

    # what the result must be: the gathered-page attention in float32
    def reference():
        from sutro_tpu.engine.kvcache import gather_kv_layer
        from sutro_tpu.ops.attention import chunk_attention

        gk, gv = gather_kv_layer(kp, vp, zero, table_d, KVH)
        return chunk_attention(
            q[:, None].astype(jnp.float32), kc[:, None].astype(jnp.float32),
            vc[:, None].astype(jnp.float32),
            positions=(past_d + 4)[:, None],
            valid_len=jnp.ones((B,), jnp.int32),
            past_k=gk.astype(jnp.float32), past_v=gv.astype(jnp.float32),
            past_len=past_d, window=zero,
            win_k=wk.astype(jnp.float32), win_v=wv.astype(jnp.float32),
            win_len=jnp.asarray(4, jnp.int32),
        )[:, 0]

    want = np.asarray(reference(), np.float32)
    live = pages > 0
    needed = max(float((past / PS).sum()), 1e-9)
    out = {
        "tree": args.tree, "shape": args.shape, "scatter": args.scatter,
        "seed": args.seed, "set": args.set, "past": args.past,
        "device": dev.device_kind,
        "rows_live": int(live.sum()), "mean_past": float(past[live].mean()),
        "pages_needed_a_call": round(needed, 2),
        "kv_mb_needed_a_call": round(needed * 2 * PS * KD * 2 / 1e6, 2),
        "runs": [],
    }
    from sutro_tpu.ops import lowering

    # the rows a grid step the tree built its kernel with (a tree from
    # before the counter ran one)
    built = getattr(lowering, "paged_decode_rows_per_step", dict)
    for ch in chunks:
        kw = dict(rows_kw)
        if ch is not None:
            kw["kv_chunk"] = ch
        one = pallas_paged.paged_decode_attention(
            q, kp, vp, zero, table_d, past_d, kc, vc, zero, None,
            wk, wv, jnp.asarray(4, jnp.int32), **kw,
        )
        err = float(
            np.abs(np.asarray(one, np.float32) - want)[live].max()
        )
        step = step_fn(ch)
        a = (q, kp, vp, table_d, past_d, kc, vc, wk, wv)
        step(*a).block_until_ready()
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            step(*a).block_until_ready()
            times.append(time.perf_counter() - t0)
        med = float(np.median(times))
        # pages a row fetches: whole chunks at the parent, else its own
        pp = ch or 1
        fetched = float((-(-past // (pp * PS)) * pp).sum())
        out["runs"].append({
            "kv_chunk": ch,
            "rows_per_step": max(built(), default=1),
            "step_ms": round(med * 1e3, 4),
            "call_us": round(med / CALLS * 1e6, 2),
            "row_us": round(med / CALLS / max(int(live.sum()), 1) * 1e6, 3),
            "min_step_ms": round(min(times) * 1e3, 4),
            "pages_fetched_a_call": fetched,
            "fetched_over_needed": round(fetched / needed, 3),
            "needed_gb_per_s": round(
                needed * 2 * PS * KD * 2 / (med / CALLS) / 1e9, 1
            ),
            "max_abs_err_vs_f32_reference": err,
        })
    print(json.dumps(out))


if __name__ == "__main__":
    main()
