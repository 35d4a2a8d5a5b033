"""Interactive-tier latency benchmark: TTFT/ITL for the serving path.

Three legs through ``LocalEngine`` + ``InteractiveGateway`` (the same
code path POST /v1/chat/completions takes, minus HTTP framing):

- **idle**: interactive requests against an otherwise-empty engine —
  the TTFT floor the co-resident leg is graded against.
- **batch_alone**: the reference batch job by itself (rows/hour
  baseline for the throughput-retention grade).
- **cobatch**: the same batch job with interactive requests streaming
  against it — latency-priority admission evicts batch rows via the
  pause/resume primitive (EngineConfig.interactive_slots budget).

Acceptance targets (ISSUE 9 / PERF.md): cobatch p99 TTFT < 5x idle
TTFT, batch rows/hour within 20% of batch_alone. On TPU the batch leg
defaults to 20k rows; the CPU smoke is time-boxed via env overrides
(SUTRO_IBENCH_ROWS / SUTRO_IBENCH_REQS / SUTRO_IBENCH_MAXTOK).

Writes BENCH_INTERACTIVE.json and prints one JSON line per leg.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

from bench_e2e import make_reviews


def pct(samples, q):
    if not samples:
        return None
    xs = sorted(samples)
    i = min(len(xs) - 1, max(0, round(q / 100.0 * (len(xs) - 1))))
    return round(xs[i], 4)


def main() -> None:
    from sutro_tpu.engine.softdeadline import arm_from_env

    arm_from_env()
    import jax

    on_tpu = jax.default_backend() not in ("cpu",)

    if on_tpu:
        model = os.environ.get("SUTRO_E2E_MODEL", "qwen-3-0.6b")
        rows = int(os.environ.get("SUTRO_IBENCH_ROWS", "20000"))
        n_reqs = int(os.environ.get("SUTRO_IBENCH_REQS", "20"))
        max_tok = int(os.environ.get("SUTRO_IBENCH_MAXTOK", "64"))
        ecfg = dict(
            decode_batch_size=64, kv_page_size=64, max_pages_per_seq=8,
            max_model_len=512, max_new_tokens=max_tok,
            interactive_slots=2,
        )
    else:  # CPU smoke
        model = "tiny-dense"
        rows = int(os.environ.get("SUTRO_IBENCH_ROWS", "48"))
        n_reqs = int(os.environ.get("SUTRO_IBENCH_REQS", "4"))
        max_tok = int(os.environ.get("SUTRO_IBENCH_MAXTOK", "8"))
        ecfg = dict(
            decode_batch_size=4, kv_page_size=8, max_pages_per_seq=16,
            max_model_len=128, max_new_tokens=max_tok, use_pallas=False,
            param_dtype="float32", interactive_slots=2,
        )

    os.environ.setdefault("SUTRO_HOME", "/tmp/sutro-bench-interactive")
    from sutro_tpu.sdk import Sutro
    from sutro_tpu.serving import openai as oai
    from sutro_tpu.serving.openai import parse_request

    so = Sutro(engine_config=ecfg)
    eng = so.engine
    gw = eng.gateway
    assert gw is not None, "interactive_slots must be > 0"
    results = {}

    def one_request(i, ttfts, itls, content=None, warm_toks=None):
        body = {
            "model": model,
            "messages": [
                {
                    "role": "user",
                    "content": content
                    or f"Question {i}: say something.",
                }
            ],
            "max_tokens": max_tok,
            "stream": True,
        }
        ir = gw.submit(parse_request(body, chat=True))
        for _ in oai.iter_stream(ir, chat=True):
            pass
        ttft = ir.channel.ttft_s()
        if ttft is not None:
            ttfts.append(ttft)
        itls.extend(ir.channel.itl_samples)
        if warm_toks is not None:
            # submit-time store probe (serving/gateway.py): how many
            # leading prompt tokens already had resident KV
            warm_toks.append(ir.warm_tokens)

    def latency_leg(name, content_fn=None):
        ttfts, itls, warm_toks = [], [], []
        threads = [
            threading.Thread(
                target=one_request,
                args=(i, ttfts, itls),
                kwargs={
                    "content": content_fn(i) if content_fn else None,
                    "warm_toks": warm_toks,
                },
            )
            for i in range(n_reqs)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
            # staggered open-loop-ish arrivals, not a thundering herd
            time.sleep(0.05)
        for t in threads:
            t.join()
        entry = {
            "n_requests": n_reqs,
            "max_tokens": max_tok,
            "elapsed_s": round(time.monotonic() - t0, 2),
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "itl_p50_s": pct(itls, 50),
            "itl_p99_s": pct(itls, 99),
            "warm_prefix_tokens_total": sum(warm_toks),
        }
        results[name] = entry
        print(json.dumps({name: entry}), flush=True)
        return entry

    def batch_job(tag):
        # salt the rows per leg: identical payloads would hit the
        # jobstore's result reuse and record a no-op as "throughput"
        t0 = time.monotonic()
        jid = so.infer(
            [f"[{tag}] {r}" for r in make_reviews(rows)],
            model=model,
            system_prompt="Summarize the review in one short sentence.",
            stay_attached=False,
        )
        df = so.await_job_completion(jid, timeout=24 * 3600)
        assert df is not None and len(df) == rows, "batch job lost rows"
        elapsed = time.monotonic() - t0
        return {
            "rows": rows,
            "elapsed_s": round(elapsed, 2),
            "rows_per_hour": round(rows / elapsed * 3600, 1),
        }

    # -- leg 1: idle latency floor -------------------------------------
    # warm the runner so leg 1's first TTFT is not a model-load stall
    one_request(-1, [], [])
    latency_leg("idle")

    # -- leg 1b: warm-prefix TTFT (engine-lifetime radix store) --------
    # The same long prompt shell with per-request tails, twice: the
    # cold pass prefills the shell per request, the warm pass finds its
    # KV resident in the prefix store and prefills only the tail — the
    # warm p99 TTFT must come in below cold (graded below). A same-
    # length throwaway shell first primes BOTH prefill compile buckets
    # (full shell + short tail) so neither pass eats an XLA compile.
    if on_tpu:
        shell = (
            "Support agent context: orders ship within two business "
            "days; returns are accepted for thirty days with receipt; "
            "warranty claims need the serial number; gift wrapping is "
            "free over fifty dollars; loyalty points expire yearly. "
            "Answer the customer's question in one short sentence."
        )
    else:
        # sized for the 128-token smoke context (shell still dominant)
        shell = (
            "Orders ship in two days; returns accepted for thirty "
            "days. Reply briefly."
        )
    prime = ("The quick brown fox jumps over the lazy dog. " * 12)[
        : len(shell)
    ]
    one_request(-2, [], [], content=prime + " a")
    one_request(-3, [], [], content=prime + " b")
    latency_leg("prefix_cold", lambda i: f"{shell} item {i}")
    latency_leg("prefix_warm", lambda i: f"{shell} item {i}")

    # -- leg 1c: session hibernation (tiered KV pool) ------------------
    # S sticky chat sessions each hold a turn of transcript KV — far
    # more KV than the HBM pool holds at once, so finished turns
    # checkpoint into the prefix store and pressure-demote host-ward
    # (SUTRO_KV_TIERS). An idle sweep (gateway.checkpoint_idle) then
    # hibernates every session, and turn 2 resumes each by prefix-hit
    # or tier promotion instead of re-prefilling its history. Graded:
    # resume p99 TTFT vs cold p99 TTFT, the sessions' total KV pages
    # vs the HBM page budget (the >= 10x session-scale bar), and zero
    # lost turns.
    n_sessions = int(
        os.environ.get(
            "SUTRO_IBENCH_SESSIONS", "256" if on_tpu else "144"
        )
    )
    os.environ["SUTRO_KV_TIERS"] = "1"
    try:
        if on_tpu:
            opener = (
                "My order number is 81{i:04d} and my favorite color "
                "is teal. Remember both and acknowledge briefly."
            )
            follow = "What is my order number?"
        else:  # sized for the 128-token smoke context, two turns deep
            opener = "Order 81{i:03d}, color teal. Remember."
            follow = "Order number?"

        def session_turn(sid, content):
            body = {
                "model": model,
                "messages": [{"role": "user", "content": content}],
                "max_tokens": max_tok,
                "temperature": 0.0,
                "stream": True,
                "session_id": sid,
            }
            ir = gw.submit(parse_request(body, chat=True))
            fin = None
            for chunk in oai.iter_stream(ir, chat=True):
                if chunk is None:  # heartbeat gap
                    continue
                fin = chunk["choices"][0].get("finish_reason") or fin
            return ir.channel.ttft_s(), fin

        # a LOST row is a turn that never reached a clean terminal
        # state; an empty completion (immediate stop) is legal and
        # simply contributes no TTFT sample
        cold_ttfts, resume_ttfts, lost = [], [], 0
        for i in range(n_sessions):
            ttft, fin = session_turn(
                f"bench-s{i}", opener.format(i=i)
            )
            if fin not in ("stop", "length"):
                lost += 1
            elif ttft is not None:
                cold_ttfts.append(ttft)
        sess_pages = sum(
            len(s.ids) // ecfg["kv_page_size"]
            for k, s in gw._sessions.items()
            if k[1].startswith("bench-s")
        )
        posted = gw.checkpoint_idle(idle_s=0.0)
        for i in range(n_sessions):
            ttft, fin = session_turn(f"bench-s{i}", follow)
            if fin not in ("stop", "length"):
                lost += 1
            elif ttft is not None:
                resume_ttfts.append(ttft)
        pool = eng._kv_tiers.get(model)
        census = pool.op_census() if pool is not None else {}
        runner_tok = eng._runner_cache.get(model)
        hbm_pages = None
        if runner_tok is not None:
            r0 = runner_tok[0]
            hbm_pages = int(getattr(r0, "alloc_pages", r0.num_pages))
        entry = {
            "n_sessions": n_sessions,
            "idle_checkpoints_posted": posted,
            "session_kv_pages": sess_pages,
            "hbm_pool_pages": hbm_pages,
            "cold_ttft_p50_s": pct(cold_ttfts, 50),
            "cold_ttft_p99_s": pct(cold_ttfts, 99),
            "resume_ttft_p50_s": pct(resume_ttfts, 50),
            "resume_ttft_p99_s": pct(resume_ttfts, 99),
            "lost_rows": lost,
            "tier_census": census,
        }
        results["hibernate_resume"] = entry
        print(json.dumps({"hibernate_resume": entry}), flush=True)
        assert lost == 0, "hibernate/resume leg lost session turns"
    finally:
        os.environ.pop("SUTRO_KV_TIERS", None)

    # -- leg 2: batch throughput baseline ------------------------------
    # warm the batch path (prefill/decode compile at batch shapes) so
    # the baseline leg measures steady-state throughput, not JIT —
    # same review rows as the measured legs so the shape buckets match
    jid = so.infer(
        [
            f"[warm] {r}"
            for r in make_reviews(
                min(rows, 4 * ecfg["decode_batch_size"])
            )
        ],
        model=model,
        system_prompt="Summarize the review in one short sentence.",
        stay_attached=False,
    )
    so.await_job_completion(jid, timeout=24 * 3600, obtain_results=False)
    entry = batch_job("alone")
    results["batch_alone"] = entry
    print(json.dumps({"batch_alone": entry}), flush=True)

    # -- leg 3: interactive against the live batch ---------------------
    done = {}

    def run_batch():
        done.update(batch_job("cobatch"))

    bt = threading.Thread(target=run_batch)
    bt.start()
    # let the batch session occupy the decode window before probing it
    time.sleep(1.0 if on_tpu else 0.2)
    entry = latency_leg("cobatch")
    bt.join()
    results["cobatch"].update({"batch": dict(done)})
    print(json.dumps({"cobatch_batch": done}), flush=True)

    idle99 = results["idle"]["ttft_p99_s"] or 0.0
    co99 = results["cobatch"]["ttft_p99_s"] or 0.0
    base_rph = results["batch_alone"]["rows_per_hour"]
    co_rph = done["rows_per_hour"]
    pc99 = results["prefix_cold"]["ttft_p99_s"] or 0.0
    pw99 = results["prefix_warm"]["ttft_p99_s"] or 0.0
    hib = results["hibernate_resume"]
    hc99 = hib["cold_ttft_p99_s"] or 0.0
    hr99 = hib["resume_ttft_p99_s"] or 0.0
    results["grades"] = {
        "ttft_p99_ratio_vs_idle": (
            round(co99 / idle99, 2) if idle99 else None
        ),
        "ttft_target": "p99 cobatch < 5x idle",
        "batch_throughput_retention": round(co_rph / base_rph, 3),
        "throughput_target": "cobatch batch rows/hour >= 0.8x alone",
        "warm_prefix_ttft_p99_ratio": (
            round(pw99 / pc99, 3) if pc99 else None
        ),
        "warm_prefix_target": "p99 warm < 1x cold (shell KV resident)",
        "resume_ttft_p99_ratio_vs_cold": (
            round(hr99 / hc99, 3) if hc99 else None
        ),
        "resume_target": "p99 resume <= 0.5x cold (upload, not re-prefill)",
        "session_kv_vs_hbm_pages": (
            round(hib["session_kv_pages"] / hib["hbm_pool_pages"], 2)
            if hib["hbm_pool_pages"]
            else None
        ),
        "session_scale_target": "session KV >= 10x the HBM page budget",
        "session_lost_rows": hib["lost_rows"],
    }
    print(json.dumps({"grades": results["grades"]}), flush=True)

    out = {
        "backend": jax.default_backend(),
        # devices the runner's mesh spans (the per-chip divisor) and
        # every device the host has
        "n_chips": eng.ecfg.mesh_devices(jax.device_count()),
        "host_devices": jax.device_count(),
        "model": model,
        "interactive_slots": ecfg["interactive_slots"],
        "legs": results,
    }
    Path(__file__).parent.joinpath("BENCH_INTERACTIVE.json").write_text(
        json.dumps(out, indent=2)
    )
    print(json.dumps({"bench_interactive": "written"}), flush=True)


if __name__ == "__main__":
    main()
