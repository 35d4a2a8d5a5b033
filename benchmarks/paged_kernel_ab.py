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
  table starting with the same shared prefix pages;
- ``joyai`` / ``xing`` / ``glm5``: the LATENT variant (``v_pages`` None:
  one pool of 640-wide rows, every head to the one stored row) at those
  cells' batch, heads, table and contexts (``LATENT`` below), beside the
  XLA form of the same call (``ops/attention.latent_attention``: the
  rows' tables gathered): ms a call and GB/s of the ROWS WALKED (a row's
  pages up to its last token's);
- ``glm5-selected``: the glm5 shape under a row's SELECTION. Kernel
  alone: ``keep`` a random ``--keep-share`` of a row's positions.
  Then ``ops/sparse_attention.selected_decode`` whole (index scores over
  a 128-wide index pool, the top ``index_topk`` 2,048, the attention)
  with ``use_pallas`` on (the mask and the kernel) and off (``lax.top_k``
  and the row gather) on the same operands, and their largest difference.

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
import functools
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
#: the latent cells: batch, heads, table pages, a row's past (tokens, low
#: and high: prompt + template + somewhere along its output), and what
#: the configuration's scale is; 5 layers of 640-wide rows, 20 calls a
#: timed step, a window buffer of 15 slots of which 7 hold rows
LATENT = {
    "joyai": dict(B=32, NH=32, MP=64, past=(1120, 3580), scale=192 ** -0.5),
    "xing": dict(B=128, NH=32, MP=64, past=(1120, 3580), scale=192 ** -0.5),
    "glm5": dict(B=16, NH=64, MP=256, past=(6280, 7740), scale=1 / 16),
    "glm5-selected": dict(
        B=16, NH=64, MP=256, past=(6280, 7740), scale=1 / 16, topk=2048,
    ),
}
LATENT_WIDTH, LATENT_VALUES, LATENT_LAYERS, LATENT_CALLS = 640, 512, 5, 20
LATENT_WINDOW, LATENT_PENDING = 15, 7


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


def _times(step, operands, reps: int) -> list:
    """Seconds of ``reps`` runs of ``step(*operands)``, compiled and run
    once first."""
    step(*operands).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        step(*operands).block_until_ready()
        times.append(time.perf_counter() - t0)
    return times


def latent(args, dev) -> dict:
    """A latent shape's result line: the kernel and the XLA form a call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops import lowering, pallas_paged, sparse_attention
    from sutro_tpu.ops.attention import latent_attention

    sh = LATENT[args.shape]
    B, NH, MPl, scale = sh["B"], sh["NH"], sh["MP"], sh["scale"]
    topk = sh.get("topk")
    W, L, N = LATENT_WIDTH, LATENT_LAYERS, LATENT_CALLS
    if topk and "keep" not in inspect.signature(
        pallas_paged.paged_decode_attention
    ).parameters:
        raise SystemExit(f"{args.tree}'s kernel takes no selection")
    rng = np.random.default_rng(args.seed)
    past = rng.integers(*sh["past"], B) if args.past is None else (
        np.full((B,), args.past)
    )
    NP = 1 + B * MPl
    table = 1 + np.arange(B * MPl, dtype=np.int32).reshape(B, MPl)
    if args.scatter:
        table = 1 + rng.permutation(B * MPl).astype(np.int32).reshape(B, MPl)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    if args.cpu:
        bf = jnp.float32
    pool = jax.random.normal(ks[0], (L, NP, PS, W), bf)
    q = jax.random.normal(ks[1], (B, 1, NH, W), bf)
    row = jax.random.normal(ks[2], (B, 1, W), bf)
    win = jax.random.normal(ks[3], (B, LATENT_WINDOW, W), bf)
    table_d, past_d = jnp.asarray(table), jnp.asarray(past, jnp.int32)
    pending = jnp.asarray(LATENT_PENDING, jnp.int32)
    rows_kw = {} if args.rows is None else {"rows": args.rows}
    kw = dict(
        positions=(past_d + pending)[:, None],
        valid_len=jnp.ones((B,), jnp.int32), scale=scale,
        page_table=table_d, past_len=past_d, win_len=pending,
        value_width=LATENT_VALUES,
    )

    def looped(one):
        """``one(layer, *operands)`` -> [B, NH, x], N calls a step."""

        @jax.jit
        def step(*operands):
            def body(i, acc):
                return acc + one(i % L, *operands).astype(jnp.float32)

            first = jax.eval_shape(lambda: one(0, *operands))
            return jax.lax.fori_loop(
                0, N, body, jnp.zeros(first.shape, jnp.float32)
            )

        return step

    def kernel(layer, q, pool, row, win, *selection):
        sel = dict(zip(("keep", "keep_tail"), selection))
        return pallas_paged.paged_decode_attention(
            q[:, 0], pool, None, layer, table_d, past_d, row, None,
            jnp.int32(0), win_k=win, win_len=pending, scale=scale,
            **sel, **rows_kw,
        )[..., :LATENT_VALUES]

    def xla(layer, q, pool, row, win):
        return latent_attention(
            q, row, None, pages=pool, layer=layer, win_rows=win, **kw
        )[:, 0]

    walked = float((-(-past // PS)).sum()) * PS * W * 2
    out = {
        "tree": args.tree, "shape": args.shape, "scatter": args.scatter,
        "seed": args.seed, "device": dev.device_kind, "rows": B, "heads": NH,
        "mean_past": float(past.mean()),
        "walked_mb_a_call": round(walked / 1e6, 2),
    }

    def reading(name, one, given):
        seconds = float(np.median(_times(looped(one), given, args.reps))) / N
        out[name + "_ms_a_call"] = round(seconds * 1e3, 4)
        out[name + "_walked_gb_per_s"] = round(walked / seconds / 1e9, 1)

    operands = (q, pool, row, win)
    if topk:
        # the kernel alone under a random selection of the share asked
        keep = jax.random.uniform(ks[4], (B, MPl * PS)) < args.keep_share
        tail = jnp.ones((B, LATENT_WINDOW + 1), bool)
        reading("kernel", kernel, operands + (keep, tail))
        out["keep_share"] = args.keep_share
        # the whole selecting step a layer, both ways
        ipool = jax.random.normal(ks[5], (L, NP, PS, 128), bf)
        index = dict(
            q=jax.random.normal(ks[6], (B, 1, 32, 128), bf),
            w=jax.random.uniform(ks[7], (B, 1, 32), jnp.float32),
            k=jax.random.normal(ks[4], (B, 1, 128), bf),
            win=jax.random.normal(ks[5], (B, LATENT_WINDOW, 128), bf),
        )

        def selected(use_pallas):
            def one(layer, q, pool, row, win, ipool, index):
                return sparse_attention.selected_decode(
                    q, row,
                    sparse_attention.Indexer(topk=topk, pages=ipool, **index),
                    pages=pool, layer=layer, win_rows=win,
                    use_pallas=use_pallas, **kw,
                )[:, 0]

            return one

        both = operands + (ipool, index)
        for name, on in (("selected_kernel", True), ("selected_xla", False)):
            reading(name, selected(on), both)
        got, want = (
            np.asarray(selected(on)(0, *both), np.float32) for on in (True, False)
        )
    else:
        reading("kernel", kernel, operands)
        reading("xla", xla, operands)
        got, want = (
            np.asarray(f(0, *operands), np.float32) for f in (kernel, xla)
        )
    out["max_abs_diff_kernel_xla"] = float(np.abs(got - want).max())
    out["rows_per_step"] = max(lowering.paged_decode_rows_per_step(), default=1)
    forms = getattr(lowering, "paged_decode_forms", dict)
    out["paged_decode"] = {**lowering.snapshot()["paged_decode"], **forms()}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=".")
    ap.add_argument(
        "--shape", choices=sorted(SHAPES) + sorted(LATENT), default="4b"
    )
    ap.add_argument(
        "--keep-share", type=float, default=0.29,
        help="glm5-selected, the kernel alone: the share of a row's "
        "positions its random selection keeps",
    )
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
    ap.add_argument(
        "--cpu", action="store_true",
        help="a latent shape at a tiny size with the kernel interpreted",
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
    if args.cpu and args.shape in LATENT:
        # the control flow at a tiny size, the kernel interpreted: no speed
        LATENT[args.shape].update(B=4, MP=4, past=(130, 250))
        if "topk" in LATENT[args.shape]:
            LATENT[args.shape]["topk"] = 64
        pallas_paged.paged_decode_attention = functools.partial(
            pallas_paged.paged_decode_attention, interpret=True
        )
        args.reps = 1
    elif dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    if args.shape in LATENT:
        print(json.dumps(latent(args, dev)))
        return
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
        times = _times(step, a, args.reps)
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
