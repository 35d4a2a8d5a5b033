"""Where a decode step and a one-row prefill spend the device's time, BY
SCOPE, at a benchmark configuration's own sizes.

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
op's self time goes to the first ``jax.named_scope`` of the mixed walk in
its HLO ``op_name`` (``moe_ffn``, ``shared_expert`` inside it,
``attn_window``, ``attn_full``,
``attn_mixer`` (``gqa_gate`` inside it), ``conv_mixer``, ``mamba_mixer``,
``kda_mixer`` (inside it ``kda_conv``, ``kda_chunk``, ``kda_state_read``),
``kda_commit``, ``mla_mixer`` and inside it
``mla_expand`` or ``mla_absorb`` and, under an indexer, ``dsa_indexer``,
``dsa_select`` and ``dsa_attend`` (the innermost wins), ``dense_ffn``; ``other``
is the head, sampling, embeddings and what XLA hoisted), read from the
event's own HLO line or, where the trace leaves it out, from the
optimized HLO the compiler dumped (``--xla_dump_to``, set here before JAX
loads). Prints one JSON line a program: ms a run (a decode STEP for the
window), by scope, and the largest ops of each scope; then which paths
the process traced (``ops/lowering.py``: the kernels, the grouped
product, a routed layer's combine).

``--top N`` lists N ops a scope (4), ``--by-name`` each under its own HLO
name (``copy.1227``) and not summed by kind: the name to look up in the
optimized HLO.

What ``perfbench/trace_reduce.py`` cannot say: it keys an op by its own
name and drops ``op_name`` (PERF.md section 7 row 19). Fails without a
TPU unless ``--cpu`` (the rehearsal configuration, to debug the flow).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

SCOPES = (
    "moe_ffn", "attn_window", "attn_full", "attn_mixer", "conv_mixer",
    "mamba_mixer", "mla_mixer", "kda_mixer", "kda_commit", "dense_ffn",
    "paged_decode_xla",
)
#: scopes INSIDE one of the above that are told apart: the shared expert
#: inside ``moe_ffn``, the products of the form taken inside ``mla_mixer``
#: and, inside those, an indexer's scores, its selection and the
#: attention over it
INNER = ("shared_expert", "mla_expand", "mla_absorb", "dsa_indexer",
         "dsa_select", "dsa_attend", "kda_conv", "kda_chunk",
         "kda_state_read", "gqa_gate")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DEF = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")


def scope_of(op_name: str) -> str:
    parts = op_name.split("/")
    inner = next((p for p in reversed(parts) if p in INNER), None)
    return inner or next((p for p in parts if p in SCOPES), "other")


def scopes_from_dump(dump: Path) -> dict:
    """``{module name: {op name: scope}}`` from the optimized HLO texts."""
    out: dict = {}
    for path in dump.glob("*after_optimizations.txt"):
        text = path.read_text()
        head = re.search(r"HloModule ([\w.\-]+)", text)
        ops = out.setdefault(head.group(1) if head else path.name, {})
        for line in text.splitlines():
            name, meta = _DEF.match(line), _OP_NAME.search(line)
            if name and meta:
                ops[name.group(1)] = scope_of(meta.group(1))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompts", type=int, nargs="+", default=[1100, 1800, 2600, 3400])
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--masked-steps", type=int, default=0)
    ap.add_argument("--top", type=int, default=4,
                    help="ops listed a scope, the costliest first")
    ap.add_argument("--by-name", action="store_true",
                    help="list ops under their own HLO names (copy.1227), "
                    "not summed by kind (copy)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    dump = Path(tempfile.mkdtemp(prefix="scopes-hlo-"))
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_dump_to={dump} --xla_dump_hlo_as_text"
        " --xla_dump_hlo_module_re=.*(prefill|decode).*"
    )
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from perfbench import trace_reduce
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
    runner.release_window_behind(tables, lens)
    temp, top_p = np.full((B,), 0.7, np.float32), np.full((B,), 0.95, np.float32)
    last = rng.integers(0, mcfg.vocab_size, B).astype(np.int32)
    past = lens.astype(np.int32)

    def window(i):
        nonlocal last, past
        toks, _ = runner.decode_multi(
            last, past, tables, jax.random.PRNGKey(i), temp, top_p, steps
        )
        last, past = np.asarray(toks[-1]), past + steps
        runner.release_window_behind(tables, past)  # the scheduler's part
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
        runner.release_window_behind(tables, past)

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
    from_dump = scopes_from_dump(dump)
    data = ProfileData.from_file(str(xplane))
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name.lower(): list(line.events) for line in plane.lines}
        ops = lines.get("xla ops") or lines.get("ops") or []
        modules = lines.get("xla modules") or lines.get("modules") or []
        if not ops or not modules:
            continue
        events = [
            [ev.name, float(ev.start_ns), float(ev.duration_ns)] for ev in ops
        ]
        selfs = trace_reduce.self_times(events)
        runs = [
            (trace_reduce.module_key(m.name), float(m.start_ns),
             float(m.start_ns + m.duration_ns)) for m in modules
        ]
        by = defaultdict(lambda: defaultdict(lambda: defaultdict(float)))
        unscoped = 0
        for (name, start, _dur), self_ns in zip(events, selfs):
            module = next((m for m, lo, hi in runs if lo <= start < hi), None)
            if module is None:
                continue
            short = trace_reduce.split_hlo(name)[0]
            meta = _OP_NAME.search(name)
            if meta:
                scope = scope_of(meta.group(1))
            else:
                unscoped += 1
                scope = next(
                    (ops_[short] for mod, ops_ in from_dump.items()
                     if mod in module and short in ops_), "other",
                )
            key = short if args.by_name else trace_reduce.op_key(short)
            by[module][scope][key] += self_ns
        for module, scopes in sorted(by.items()):
            n = sum(1 for m, _lo, _hi in runs if m == module)
            per = n * (steps if "decode_multi" in module else 1)
            print(json.dumps({
                "program": module, "runs": n,
                "unit": "step" if "decode_multi" in module else "run",
                "ms": sum(sum(o.values()) for o in scopes.values()) / per / 1e6,
                "by_scope_ms": {
                    s: round(sum(o.values()) / per / 1e6, 3)
                    for s, o in sorted(
                        scopes.items(), key=lambda kv: -sum(kv[1].values())
                    )
                },
                "top_ops_ms": {
                    s: {k: round(v / per / 1e6, 3) for k, v in sorted(
                        o.items(), key=lambda kv: -kv[1])[:args.top]}
                    for s, o in scopes.items()
                },
                "ops_scoped_from_dump": unscoped,
            }), flush=True)
        break
    else:
        print(json.dumps({
            "problem": "the trace holds no device plane",
            "ops_in_the_dump_by_scope": {
                mod: {s: list(ops_.values()).count(s) for s in set(ops_.values())}
                for mod, ops_ in from_dump.items()
            },
        }))
    # which paths this process built into those programs
    from sutro_tpu.ops import lowering

    print(json.dumps({
        "kernel_paths": lowering.snapshot(),
        "grouped_matmul": lowering.grouped_matmul_counts(),
        "moe_combine": lowering.moe_combine_counts(),
    }), flush=True)


if __name__ == "__main__":
    main()
