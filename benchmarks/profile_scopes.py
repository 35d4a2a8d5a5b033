"""Where a decode step and a one-row prefill spend the device's time, BY
PART of the model and by scope inside it, at a benchmark configuration's
own sizes.

    python benchmarks/profile_scopes.py \\
        --config perfbench/configs/mellum2-12b-a2.5b-l8-v5e1.json \\
        --prompts 1100 1800 2600 3400

Builds the configuration's runner (seeded weights, as the cell does),
prefills the decode batch one row at a time with prompts cycling through
``--prompts``, runs a few fused decode windows, and traces the last
prefill of each bucket and two windows with the JAX profiler; with
``--masked-steps N`` also N FSM-masked greedy single steps
(``decode_step(allowed=...)``, each row's mask 256 ids of the vocabulary,
what a byte tokenizer's FSM allows: the step a constrained greedy batch
takes while its windows are being refused, ``_decode_jit``). Each device
op's self time goes to the PART its ``op_name`` opens with
(``sutro_tpu/ops/lowering.py`` ``PARTS``) and, under it, to the named
scopes inside (``mixer/attn_window``, ``ffn/moe_ffn/shared_expert``,
``mixer/mla_mixer/mla_absorb/dsa_attend``, ``cache/kda_commit``, and for a
residual stream of several lanes ``mixer/hc_coeff``, ``ffn/hc_sinkhorn``,
``mixer/hc_read``, ``ffn/hc_write``, ``mixer/mla_mixer/mla_yarn``, and
for a model that generates by blocks ``bd_denoise`` / ``bd_commit`` round
the two kinds of forward and ``sample/bd_confidence``,
``sample/bd_transfer``;
OBSERVABILITY.md "Parts of a step" lists them), read from the trace's
OWN optimized HLO by ``perfbench/trace_parts.py``: the reducer of the
benchmark's ``decode_*_ms_per_step`` metrics and of
``perfbench/tools/part_table.py``, which prints the same table for a
cell's real traffic. Prints one JSON line a program: ms a run (a decode
STEP for the window), by part, by scope, and the largest ops of each
scope; then which paths the process traced (``ops/lowering.py``: the
kernels, the grouped product, a routed layer's combine).

``--top N`` lists N ops a scope (4), ``--by-name`` each under its own HLO
name (``copy.1227``) and not summed by kind: the name to look up in the
optimized HLO.

A program whose ops carry no part at all was loaded from a compile cache
written before the scopes were (a scope is debug info, which the cache's
key leaves out): clear ``.xla_cache/`` or ``JAX_COMPILATION_CACHE_DIR``.
Fails without a TPU unless ``--cpu`` (the rehearsal configuration, to
debug the flow: a CPU trace has no device plane).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

NO_PART = "(no part)"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompts", type=int, nargs="+", default=[1100, 1800, 2600, 3400])
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--masked-steps", type=int, default=0)
    ap.add_argument("--denoising-steps", type=int, default=2,
                    help="denoising forwards a block, for a model that "
                         "generates by blocks (the unit is then a FORWARD)")
    ap.add_argument("--top", type=int, default=4,
                    help="ops listed a scope, the costliest first")
    ap.add_argument("--by-name", action="store_true",
                    help="list ops under their own HLO names (copy.1227), "
                    "not summed by kind (copy)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from perfbench import trace_parts, trace_reduce
    from sutro_tpu.ops.lowering import PARTS
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --cpu with a rehearsal config)")
    cfg = json.loads(Path(args.config).read_text())
    ecfg = EngineConfig(**cfg["engine"])
    mcfg = MODEL_CONFIGS[cfg["engine_key"]]
    runner = ModelRunner(mcfg, ecfg)
    B, MP, PS = ecfg.decode_batch_size, ecfg.max_pages_per_seq, ecfg.kv_page_size
    steps = ecfg.decode_multi_step
    rng = np.random.default_rng(0)
    lens = np.array([args.prompts[b % len(args.prompts)] for b in range(B)])
    need = -(-(lens + steps * (args.windows + 1) + args.masked_steps + 2) // PS)
    assert need.max() <= MP and 1 + need.sum() <= runner.alloc_pages, (
        need.max(), MP, need.sum(), runner.alloc_pages
    )
    tables = np.zeros((B, MP), np.int32)
    at = 1
    for b in range(B):
        tables[b, :need[b]] = np.arange(at, at + need[b])
        at += need[b]
    prompts = [rng.integers(0, mcfg.vocab_size, n).astype(np.int32) for n in lens]
    # the last row of each bucket is prefilled under the profiler
    buckets = {int(2 ** np.ceil(np.log2(n))): b for b, n in enumerate(lens)}
    traced_rows = set(buckets.values())
    for b in range(B):
        if b not in traced_rows:
            runner.prefill(prompts[b], tables[b])
    for b in traced_rows:  # compile and warm every bucket outside the trace
        runner.prefill(prompts[b], tables[b])
    runner.pools.release_behind(tables, lens)
    temp, top_p = np.full((B,), 0.7, np.float32), np.full((B,), 0.95, np.float32)
    last = rng.integers(0, mcfg.vocab_size, B).astype(np.int32)
    past = lens.astype(np.int32)
    Bk = mcfg.block_length
    # a model that generates by blocks: a window of whole blocks, each
    # ``--denoising-steps`` denoising forwards and a commit
    # (``_decode_block_jit``); its rows' pasts are whole blocks
    forwards = steps // Bk * (args.denoising_steps + 1) if Bk > 1 else steps

    def block_window(i):
        nonlocal past
        toks, _, _ = runner.decode_block_async(
            np.full((B, Bk), mcfg.mask_token_id, np.int32), np.ones((B,), bool),
            past // Bk * Bk, tables, jax.random.PRNGKey(i), temp, top_p,
            steps // Bk, steps=np.full((B,), args.denoising_steps, np.int32),
            rule=np.zeros((B,), np.int32),
        )
        np.asarray(toks)
        past = past + steps

    def window(i):
        nonlocal last, past
        if Bk > 1:
            return block_window(i)
        toks, _ = runner.decode_multi(
            last, past, tables, jax.random.PRNGKey(i), temp, top_p, steps
        )
        last, past = np.asarray(toks[-1]), past + steps
        runner.pools.release_behind(tables, past)  # the scheduler's part
    allowed = np.zeros((B, mcfg.vocab_size), bool)
    allowed[:, :256] = True
    allowed = np.packbits(allowed, axis=1)  # as decode_step takes masks
    greedy = np.zeros((B,), np.float32)

    def masked_step(i):
        nonlocal last, past
        toks, _ = runner.decode_step(
            last, past, tables, jax.random.PRNGKey(1000 + i), greedy, top_p,
            allowed=allowed,
        )
        last, past = np.asarray(toks), past + 1
        runner.pools.release_behind(tables, past)

    window(0)
    window(1)
    for i in range(min(args.masked_steps, 2)):  # compile outside the trace
        masked_step(i)
    tracedir = tempfile.mkdtemp(prefix="scopes-trace-")
    with jax.profiler.trace(tracedir):
        for b in sorted(traced_rows):
            runner.prefill(prompts[b], tables[b])
        for i in range(2, args.windows):
            window(i)
        for i in range(args.masked_steps):
            masked_step(2 + i)
    xplane = sorted(Path(tracedir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    trace, names = trace_parts.parsed(str(xplane))
    runs = trace_reduce.reduce_trace(trace)["module_s"]
    by = defaultdict(lambda: defaultdict(lambda: defaultdict(float)))
    for module, name, op_name, secs in trace_parts.op_rows(
        trace, names, trace_reduce.window_of(trace)
    ):
        part = trace_parts.part_of(op_name)
        scope = NO_PART if part is None else (
            part + "/" + trace_parts.scopes_of(op_name)
        ).rstrip("/")
        by[module][scope][
            name if args.by_name else trace_reduce.op_key(name)
        ] += secs
    order = {p: i for i, p in enumerate(PARTS + (NO_PART,))}
    for module, scopes in sorted(by.items()):
        n = runs.get(module, {}).get("runs", 0.0)
        fused = "decode_multi" in module or "decode_block_jit" in module
        per = max(n, 1.0) * (forwards if fused else 1) / 1e3
        by_part = defaultdict(float)
        for scope, ops in scopes.items():
            by_part[scope.split("/")[0]] += sum(ops.values())
        print(json.dumps({
            "program": module, "runs": n,
            "unit": ("forward" if Bk > 1 else "step") if fused else "run",
            "ms": sum(by_part.values()) / per,
            "by_part_ms": {
                p: round(v / per, 3)
                for p, v in sorted(by_part.items(), key=lambda kv: order[kv[0]])
            },
            # the part above each scope: its own line first, then its scopes
            "by_scope_ms": {
                s: round(sum(o.values()) / per, 3)
                for s, o in sorted(scopes.items(), key=lambda kv: (
                    order[kv[0].split("/")[0]], -sum(kv[1].values())
                ))
            },
            "top_ops_ms": {
                s: {k: round(v / per, 3) for k, v in sorted(
                    o.items(), key=lambda kv: -kv[1])[:args.top]}
                for s, o in scopes.items()
            },
            "stale_compile_cache": "mixer" not in by_part and (
                "decode" in module or "prefill" in module
            ),
        }), flush=True)
    if not by:
        print(json.dumps({"problem": "the trace holds no device plane"}))
    # which paths this process built into those programs
    from sutro_tpu.ops import lowering

    print(json.dumps({
        "kernel_paths": lowering.snapshot(),
        "grouped_matmul": lowering.grouped_matmul_counts(),
        "moe_combine": lowering.moe_combine_counts(),
    }), flush=True)


if __name__ == "__main__":
    main()
