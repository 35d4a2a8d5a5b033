"""End-to-end BASELINE benchmarks through the full engine stack.

Unlike bench.py (raw runner decode loop), this drives jobs through
``LocalEngine`` — scheduler admission, batched prefill, FSM-constrained
decoding, tokenizer, jobstore, metrics — matching the reference's
headline workflows (/root/reference/README.md:173-192):

- **classify**: BASELINE config #4 analog — short product reviews through
  the classification template (system prompt + JSON output_schema with
  scratchpad/classification, schema-constrained decoding).
- **generate**: the same rows without a schema (unconstrained decode
  path with fused multi-step windows).
- **embed**: BASELINE config #3 analog — rows through the embedding
  model (mean-pool head, batched).
- **longgen**: BASELINE config #5 analog — 2k-token long-output
  generation stress (long decode tails, KV growth across 30+ pages).
  Needs a differently-sized engine (more pages, smaller batch), so it
  runs via ``SUTRO_E2E_WORKLOADS=longgen`` as a separate invocation;
  results merge into the same BENCH_E2E.json.

``SUTRO_E2E_WORKLOADS`` selects a comma-set of the above (default
"classify,generate,embed"). Row counts are time-boxed defaults; raise
with SUTRO_E2E_ROWS / SUTRO_E2E_EMBED_ROWS for full-dataset runs
(20k / 1M). Weights are
random — throughput is weight-value independent — so rows/hour and
tok/s/chip are real; classification *quality* is not measured here (see
tests/test_golden.py for decode correctness on real checkpoints).

Writes BENCH_E2E.json and prints one JSON line per workload.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path


REVIEW_SNIPPETS = [
    "battery life is incredible and it charges fast",
    "stopped working after two weeks, very disappointed",
    "decent value for the price but the build feels cheap",
    "exactly as described, shipping was quick",
    "the screen scratches way too easily",
    "customer support resolved my issue in minutes",
    "way too loud under load, returned it",
    "my kids love it, survived several drops already",
]


def make_reviews(n: int) -> list:
    return [
        f"Review {i}: {REVIEW_SNIPPETS[i % len(REVIEW_SNIPPETS)]} "
        f"(order #{1000 + i})"
        for i in range(n)
    ]


def main() -> None:
    from sutro_tpu.engine.softdeadline import arm_from_env

    arm_from_env()  # clean self-exit before any outer kill (see module)
    import jax

    on_tpu = jax.default_backend() not in ("cpu",)
    workloads = {
        w.strip()
        for w in os.environ.get(
            "SUTRO_E2E_WORKLOADS",
            "classify,generate,embed,sharedshell,rank_elo",
        ).split(",")
        if w.strip()
    }
    known = {
        "classify", "generate", "embed", "longgen", "sharedshell",
        "rank_elo",
    }
    if not workloads or workloads - known:
        raise SystemExit(
            f"SUTRO_E2E_WORKLOADS must name a subset of {sorted(known)}, "
            f"got {sorted(workloads)}"
        )
    long_only = workloads == {"longgen"}
    if "longgen" in workloads and not long_only:
        # the 2k-token stress needs its own engine sizing — a shared
        # engine would silently record a short-tail run as "longgen"
        raise SystemExit(
            "longgen requires its own invocation: "
            "SUTRO_E2E_WORKLOADS=longgen"
        )

    if on_tpu:
        model = os.environ.get("SUTRO_E2E_MODEL", "qwen-3-0.6b")
        emb_model = "qwen-3-embedding-0.6b"
        rows = int(os.environ.get("SUTRO_E2E_ROWS", "1024"))
        emb_rows = int(os.environ.get("SUTRO_E2E_EMBED_ROWS", "20000"))
        long_rows = int(os.environ.get("SUTRO_E2E_LONG_ROWS", "32"))
        if long_only:
            # 2k-token tails: 34 pages cover 128 prompt + 2048 new
            ecfg = dict(
                decode_batch_size=16,
                kv_page_size=64,
                max_pages_per_seq=34,
                max_model_len=2304,
                max_new_tokens=2048,
            )
        else:
            ecfg = dict(
                decode_batch_size=64,
                kv_page_size=64,
                max_pages_per_seq=8,
                max_model_len=512,
                max_new_tokens=48,
            )
    else:  # CPU smoke
        model = emb_model = "tiny-dense"
        emb_model = "tiny-emb"
        rows = int(os.environ.get("SUTRO_E2E_ROWS", "16"))
        emb_rows = int(os.environ.get("SUTRO_E2E_EMBED_ROWS", "64"))
        long_rows = int(os.environ.get("SUTRO_E2E_LONG_ROWS", "2"))
        if long_only:
            # smoke the long-tail path only: CPU decode is ~5 tok/s, so
            # the "long" output is 48 tokens, not 2k. The byte tokenizer
            # makes the system prompt ~200 tokens/row — the context must
            # cover prompt + 48 or admission truncates generation away
            ecfg = dict(
                decode_batch_size=2, kv_page_size=8, max_pages_per_seq=36,
                max_model_len=280, max_new_tokens=48, use_pallas=False,
                param_dtype="float32",
            )
        else:
            ecfg = dict(
                decode_batch_size=4, kv_page_size=8, max_pages_per_seq=16,
                max_model_len=128, max_new_tokens=16, use_pallas=False,
                param_dtype="float32",
            )

    # scheduler-path window A/B (bench.py's lockstep loop favors 16,
    # but the scheduler pays min-cap all-or-nothing tails): A/B the
    # candidate here before flipping the engine-wide default
    if os.environ.get("SUTRO_E2E_MULTI"):
        ecfg["decode_multi_step"] = int(os.environ["SUTRO_E2E_MULTI"])
    # Hydragen-style split decode over the job's shared prefix A/B
    # (Pallas path only; templated workloads here all share a system
    # prompt, which is exactly the case it accelerates)
    if os.environ.get("SUTRO_PREFIX_SPLIT"):
        ecfg["prefix_split"] = (
            os.environ["SUTRO_PREFIX_SPLIT"] == "1"
        )
    # FSM fast-forward A/B (classify is schema-constrained, so its
    # scaffold tokens ride parallel verifies by default — SUTRO_E2E_FF=0
    # measures the pre-round-4 window path)
    if os.environ.get("SUTRO_E2E_FF"):
        ecfg["constrain_fastforward"] = int(os.environ["SUTRO_E2E_FF"])

    # A/B legs must not CLOBBER the default entries in BENCH_E2E.json
    # (workloads merge by name): suffix each workload's key with the
    # active lever flags THAT AFFECT IT, so "classify" and
    # "classify+ff0" coexist and the A/B delta is readable straight
    # off the artifact — while e.g. SUTRO_E2E_GEN_TEMP never creates a
    # spurious config-identical "classify+t0" duplicate.
    def ab_for(workload: str) -> str:
        decode = workload in ("classify", "generate", "longgen")
        ab = ""
        if os.environ.get("SUTRO_PREFIX_SPLIT") == "1" and decode:
            ab += "+psplit"
        if os.environ.get("SUTRO_E2E_FF") and workload == "classify":
            ab += f"+ff{int(os.environ['SUTRO_E2E_FF'])}"
        if os.environ.get("SUTRO_E2E_MULTI") and decode:
            ab += f"+w{int(os.environ['SUTRO_E2E_MULTI'])}"
        if os.environ.get("SUTRO_E2E_GEN_TEMP") and workload in (
            "generate",
        ):
            ab += f"+t{os.environ['SUTRO_E2E_GEN_TEMP']}"
        # free-form run tag (e.g. "@2k"): lets a matched-rows baseline
        # coexist with a different-scale entry of the same workload
        ab += os.environ.get("SUTRO_E2E_TAG", "")
        return ab

    os.environ.setdefault("SUTRO_HOME", "/tmp/sutro-bench-e2e")
    from sutro_tpu.sdk import Sutro

    so = Sutro(engine_config=ecfg)
    eng = so.engine
    # per-chip rates divide by the devices the runner's mesh spans (1
    # with no mesh), not by every device the host has
    host_devices = jax.device_count()
    n_chips = eng.ecfg.mesh_devices(host_devices)
    print(json.dumps({"n_chips": n_chips, "host_devices": host_devices}))
    results = {}

    def record(name, job_id, n_rows, elapsed):
        rec = eng.get_job(job_id)
        in_tok = rec.get("input_tokens") or 0
        out_tok = rec.get("output_tokens") or 0
        total = in_tok + out_tok
        cost = rec.get("job_cost") or 0.0
        entry = {
            "model": rec["model"],
            "backend": jax.default_backend(),
            "n_chips": n_chips,
            "host_devices": host_devices,
            "rows": n_rows,
            # artifacts must self-describe: a reader of the longgen row
            # needs to see the 48-token CPU cap vs the 2048-token TPU
            # config without opening this file
            "max_new_tokens": ecfg.get("max_new_tokens"),
            "engine_config": {
                k: ecfg[k]
                for k in (
                    "decode_batch_size", "kv_page_size",
                    "max_pages_per_seq", "max_model_len",
                )
                if k in ecfg
            },
            "elapsed_s": round(elapsed, 2),
            "rows_per_hour": round(n_rows / elapsed * 3600, 1),
            "input_tokens": in_tok,
            "output_tokens": out_tok,
            "tok_s_per_chip": round(total / elapsed / n_chips, 1),
            "usd_per_1m_tokens": (
                round(cost / total * 1e6, 4) if total else None
            ),
            "status": rec["status"],
        }
        # self-grade vs the hardware roofline (VERDICT r3 weak #5).
        # Decode grade is CONSERVATIVE: output tokens over the whole
        # wall time (prefill included in the denominator), so the true
        # decode-phase fraction is >= the recorded one. Embedding is a
        # prefill-shaped workload -> MFU.
        from sutro_tpu.engine import roofline
        from sutro_tpu.engine.api import resolve_model

        engine_key, mcfg, _meta = resolve_model(rec["model"])
        cached = eng._runner_cache.get(engine_key)
        if cached is not None:
            params = cached[0].params
            device_kind = jax.devices()[0].device_kind
            if name.split("+")[0].split("@")[0] == "embed":  # A/B- or tag-suffixed too
                entry.update(
                    roofline.grade_prefill(
                        total / elapsed / n_chips,
                        n_params=roofline.param_count_of(params),
                        device_kind=device_kind,
                    )
                )
            else:
                B = ecfg.get("decode_batch_size", 64)
                avg_ctx = (in_tok + out_tok / 2) / max(n_rows, 1)
                entry.update(
                    roofline.grade_decode(
                        out_tok / elapsed / n_chips,
                        batch=B,
                        bytes_per_step=roofline.decode_bytes_per_step(
                            param_bytes=roofline.param_bytes_of(params),
                            batch=B,
                            avg_ctx=avg_ctx,
                            num_layers=mcfg.num_layers,
                            kv_heads=mcfg.num_kv_heads,
                            head_dim=mcfg.head_dim,
                            kv_dtype_bytes=2 if on_tpu else 4,
                        ),
                        device_kind=device_kind,
                    )
                )
        results[name] = entry
        print(json.dumps({name: entry}), flush=True)

    reviews = make_reviews(rows)

    # -- longgen (BASELINE config #5: 2k-token output stress) ----------
    if "longgen" in workloads:
        long_reviews = make_reviews(long_rows)
        t0 = time.monotonic()
        jid = so.infer(
            long_reviews,
            model=model,
            system_prompt=(
                "Write a detailed multi-paragraph analysis of this "
                "review: themes, sentiment, implied product issues, "
                "and suggested vendor responses."
            ),
            sampling_params={"temperature": 0.8},
            stay_attached=False,
        )
        df = so.await_job_completion(jid, timeout=24 * 3600)
        assert df is not None and len(df) == long_rows
        record("longgen" + ab_for("longgen"), jid, long_rows, time.monotonic() - t0)

    # -- classify (schema-constrained; reference README.md:124-160) ----
    if "classify" in workloads:
        t0 = time.monotonic()
        jid = so.infer(
            reviews,
            model=model,
            system_prompt=(
                "You are an expert classifier. Classify the sentiment of "
                "the review as positive, negative, or neutral."
            ),
            output_schema={
                "type": "object",
                "properties": {
                    "classification": {
                        "type": "string",
                        "enum": ["positive", "negative", "neutral"],
                    },
                },
                "required": ["classification"],
            },
            # greedy, like the classify template (templates/
            # classification.py): labels want determinism AND greedy
            # constrained rows take the speculative fused-window path —
            # the engine-default 0.7 would silently bench the masked
            # single-step path for the headline workload. The window
            # path's win is amortized DISPATCH cost, so it shows on the
            # chip (PERF.md RTT analysis), not necessarily in this CPU
            # smoke where per-step dispatch is cheap.
            sampling_params={"temperature": 0.0},
            stay_attached=False,
        )
        df = so.await_job_completion(jid, timeout=24 * 3600)
        assert df is not None and len(df) == rows
        record("classify" + ab_for("classify"), jid, rows, time.monotonic() - t0)

    # -- generate (unconstrained, fused multi-step decode) --------------
    if "generate" in workloads:
        t0 = time.monotonic()
        # SUTRO_E2E_GEN_TEMP=0 makes the batch all-greedy; default
        # keeps the engine's sampled path
        gen_sp = {}
        if os.environ.get("SUTRO_E2E_GEN_TEMP"):
            gen_sp = {
                "sampling_params": {
                    "temperature": float(
                        os.environ["SUTRO_E2E_GEN_TEMP"]
                    )
                }
            }
        jid = so.infer(
            reviews,
            model=model,
            system_prompt="Summarize the review in one short sentence.",
            stay_attached=False,
            **gen_sp,
        )
        df = so.await_job_completion(jid, timeout=24 * 3600)
        assert df is not None and len(df) == rows
        record("generate" + ab_for("generate"), jid, rows, time.monotonic() - t0)

    # -- sharedshell (cross-job radix prefix store) ----------------------
    # The SAME identical-template job twice: a long system shell over
    # short rows (80%+ of each prompt is the shared shell). The second
    # job must find the shell's KV resident in the engine-lifetime
    # prefix store (engine/prefixstore.py) and prefill only the novel
    # per-row tails — the recorded prefill_reduction_x is the ISSUE's
    # >= 2x acceptance bar. Attribution comes from the engine's own
    # per-job saved-vs-paid prefill split (telemetry job attrs).
    if "sharedshell" in workloads:
        from sutro_tpu import telemetry as _tel

        if on_tpu:
            shell = (
                "You are an expert product-review analyst. Read the "
                "review below carefully and answer with one short "
                "sentence naming the dominant sentiment, the product "
                "aspect driving it, and whether the author would "
                "plausibly buy again. Be terse and literal; never "
                "speculate beyond the text of the review."
            )
            short_rows = [
                REVIEW_SNIPPETS[i % len(REVIEW_SNIPPETS)]
                for i in range(rows)
            ]
        else:
            # the 128-token smoke context truncates a long shell away;
            # size shell + rows so the shell still dominates (80%+)
            shell = (
                "Classify the sentiment of this review as positive "
                "or negative. Answer with the label only."
            )
            short_rows = [f"item {i} ok" for i in range(rows)]

        def _shell_job():
            t0 = time.monotonic()
            jid = so.infer(
                short_rows,
                model=model,
                system_prompt=shell,
                sampling_params={"temperature": 0.0},
                stay_attached=False,
            )
            df = so.await_job_completion(jid, timeout=24 * 3600)
            assert df is not None and len(df) == rows
            return jid, time.monotonic() - t0

        def _prefill_of(jid):
            # paid prefill = shell tokens this job actually ran
            # (prefix_paid) + every row's own suffix (prompt minus the
            # job-wide shared shell, which the engine measured exactly)
            rec = eng.get_job(jid)
            pa = _tel.job(jid).attrs.get("prefix") or {}
            saved = pa.get("saved_tokens", 0)
            paid = pa.get("paid_tokens", 0)
            in_tok = rec.get("input_tokens") or 0
            shell_tok = saved + paid
            return saved, paid, paid + in_tok - rows * shell_tok, in_tok

        jid1, el1 = _shell_job()
        jid2, el2 = _shell_job()
        _, _, cold_prefill, in_tok = _prefill_of(jid1)
        saved2, _, warm_prefill, _ = _prefill_of(jid2)
        entry = {
            "model": model,
            "backend": jax.default_backend(),
            "n_chips": n_chips,
            "rows": rows,
            "cold_elapsed_s": round(el1, 2),
            "warm_elapsed_s": round(el2, 2),
            "cold_prefill_tokens": cold_prefill,
            "warm_prefill_tokens": warm_prefill,
            "warm_saved_tokens": saved2,
            "shared_fraction": (
                round(rows * (saved2 or 1) / in_tok, 3) if in_tok else None
            ),
            "prefill_reduction_x": (
                round(cold_prefill / warm_prefill, 2)
                if warm_prefill else None
            ),
        }
        name = "sharedshell" + ab_for("sharedshell")
        results[name] = entry
        print(json.dumps({name: entry}), flush=True)

    # -- rank_elo (stage-graph tournament vs client-side loop) -----------
    # A 3-round pairwise tournament over a shared-context corpus, run
    # both ways: server-side as ONE stage-graph submit per round
    # (rank map stage -> elo reduce inside the engine,
    # Rank.rank(server_side=True)) and client-side as the sequential
    # loop (rank job, pull rows, fit Elo locally). Graded on rank
    # rows/hour and on the engine-measured prefill tokens saved by the
    # shared system shell riding the prefix store — the client loop
    # runs FIRST, so every warm-prefix token the server leg saves on
    # top of it is attributable to the one-submit DAG, not leg order.
    # Both grades are warn-only in `make bench-trend`.
    if "rank_elo" in workloads:
        import pandas as pd

        from sutro_tpu import telemetry as _tel

        pair_df = pd.DataFrame(
            {
                "a": [
                    REVIEW_SNIPPETS[i % len(REVIEW_SNIPPETS)]
                    for i in range(rows)
                ],
                "b": [
                    REVIEW_SNIPPETS[(i + 3) % len(REVIEW_SNIPPETS)]
                    for i in range(rows)
                ],
            }
        )
        criteria = (
            "Which review is more useful to a prospective buyer?"
        )
        rounds = 3

        def _new_jobs_saved(before_ids):
            new = [
                j["job_id"]
                for j in eng.list_jobs()
                if j["job_id"] not in before_ids
            ]
            saved = 0
            for jid in new:
                pa = _tel.job(jid).attrs.get("prefix") or {}
                saved += int(pa.get("saved_tokens") or 0)
            return new, saved

        before = {j["job_id"] for j in eng.list_jobs()}
        t0 = time.monotonic()
        for _ in range(rounds):
            res = so.rank(
                pair_df,
                ["a", "b"],
                criteria,
                model=model,
                compute_elo=True,
                server_side=False,
                # 32 new tokens: the constrained ranking JSON is ~22
                # bytes under the byte tokenizer — the smoke default 16
                # truncates it and every ranking parses as empty
                sampling_params={"temperature": 0.0,
                                 "max_new_tokens": 32},
            )
            assert res is not None
        client_s = time.monotonic() - t0
        client_jobs, client_saved = _new_jobs_saved(before)

        before = {j["job_id"] for j in eng.list_jobs()}
        t0 = time.monotonic()
        elo_df = None
        for _ in range(rounds):
            res = so.rank(
                pair_df,
                ["a", "b"],
                criteria,
                model=model,
                compute_elo=True,
                server_side=True,
                sampling_params={"temperature": 0.0,
                                 "max_new_tokens": 32},
            )
            assert res is not None
            _, elo_df = res
        server_s = time.monotonic() - t0
        server_jobs, server_saved = _new_jobs_saved(before)
        assert elo_df is not None and set(elo_df["player"]) == {"a", "b"}
        rank_rows = rounds * rows
        entry = {
            "model": model,
            "backend": jax.default_backend(),
            "n_chips": n_chips,
            "rows": rows,
            "rounds": rounds,
            "server_elapsed_s": round(server_s, 2),
            "client_elapsed_s": round(client_s, 2),
            "server_rows_per_hour": round(rank_rows / server_s * 3600, 1),
            "client_rows_per_hour": round(rank_rows / client_s * 3600, 1),
            "server_jobs_submitted": len(server_jobs),
            "client_jobs_submitted": len(client_jobs),
            "server_prefill_tokens_saved": server_saved,
            "client_prefill_tokens_saved": client_saved,
            "prefill_tokens_saved_delta": server_saved - client_saved,
            "speedup_x": (
                round(client_s / server_s, 2) if server_s else None
            ),
        }
        name = "rank_elo" + ab_for("rank_elo")
        results[name] = entry
        print(json.dumps({name: entry}), flush=True)

    # -- embed (BASELINE config #3) --------------------------------------
    if "embed" in workloads:
        emb_reviews = make_reviews(emb_rows)
        t0 = time.monotonic()
        jid = so.infer(emb_reviews, model=emb_model, stay_attached=False)
        df = so.await_job_completion(jid, timeout=24 * 3600)
        assert df is not None and len(df) == emb_rows
        record("embed" + ab_for("embed"), jid, emb_rows, time.monotonic() - t0)

    # merge into any existing BENCH_E2E.json so separately-invoked
    # workload sets (e.g. longgen) accumulate in one artifact; every
    # entry carries its own backend/n_chips, so runs from different
    # hardware never clobber each other — same-named workloads from the
    # same backend are replaced, everything else is kept
    path = Path(__file__).parent.joinpath("BENCH_E2E.json")
    backend = jax.default_backend()
    out = {
        "backend": backend,
        "n_chips": n_chips,
        "workloads": dict(results),
    }
    if path.exists():
        try:
            prev = json.loads(path.read_text())
            merged = dict(prev.get("workloads", {}))
            for name, entry in prev.get("workloads", {}).items():
                # legacy entries lack per-entry backend; stamp them
                entry.setdefault("backend", prev.get("backend"))
                entry.setdefault("n_chips", prev.get("n_chips"))
            merged.update(results)
            out["workloads"] = merged
        except (json.JSONDecodeError, OSError):
            pass
    path.write_text(json.dumps(out, indent=2))
    print(json.dumps({"bench_e2e": "written"}), flush=True)


if __name__ == "__main__":
    main()
