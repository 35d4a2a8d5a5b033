#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

ONE process drives the main path once, through the entry points a user
calls, at the full width and depth of qwen3-4b (36 layers, hidden 2560,
vocab 151,936, bf16, random weights from a seed, byte tokenizer — no
network, nothing read outside the checkout):

  get_engine(EngineConfig) + start_server_thread  — what `sutro serve` builds
  over HTTP (SDK remote backend = the wire contract):
    generate  a free-generate batch job, mixed prompt lengths, some longer
              than prefill_chunk (chunked prefill over a paged past)
    chat      four /v1/chat/completions requests while that job runs:
              a streamed and a non-streamed one with the same
              schema-constrained body must agree at temperature 0
    classify  a schema-constrained job through the classification template
  in process (SDK local backend): the generate job once more

It FAILS (non-zero exit, no result line) unless JAX finds a TPU: it never
chooses the CPU, interpret mode or a smaller model by itself. A tiny CPU
rehearsal of the same control flow exists for debugging the script; it
must be asked for by name (--cpu-rehearsal) and says so in every line it
prints. Any failed check and any exception in any phase is a non-zero
exit — nothing here logs an error and carries on.

On success the last line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and the full document (device report, model and pool bytes, compile
seconds per program, wall seconds per phase, peak memory, kernel lowering
counts) is printed above it and written to chiprun_out/chip_smoke.json.
These are bring-up facts, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
REHEARSAL_TAG = "[CPU REHEARSAL - not a device run] "

# Explicit single-chip sizing (one 16 GB v5e): bf16, the auto Pallas path
# a user gets, interactive tier on, decode batch 64, context 1024. The KV
# pool is whatever the runner fits beside 8 GB of weights (runner.py
# _pages_that_fit) — smaller than 64 full-context rows, which is a
# supported state: the scheduler admits against free pages.
CHIP = dict(
    model="qwen-3-4b",       # catalog name
    engine_key="qwen3-4b",
    config=dict(
        param_dtype="bfloat16",
        activation_dtype="bfloat16",
        use_pallas=None,
        decode_batch_size=64,
        kv_page_size=64,
        max_pages_per_seq=16,
        max_model_len=1024,
        prefill_chunk=512,
        interactive_slots=4,
        max_new_tokens=64,
        seed=0,
    ),
    gen_rows=32, gen_new=448, long_chars=560,
    cls_rows=32, cls_new=96,
    # A constrained row on random weights commits about one token per
    # scheduler iteration while the batch rows commit a window of 8, so
    # four sequential chats must need fewer iterations than the generate
    # job's 448 / 8 = 56 to land while it runs: ~10 tokens each.
    chat_new=24,
)
# the rehearsal: same control flow at a size a CPU compiles in seconds
REHEARSAL = dict(
    model="tiny-dense",
    engine_key="tiny-dense",
    config=dict(
        param_dtype="float32",
        activation_dtype="float32",
        use_pallas=None,
        decode_batch_size=8,
        kv_page_size=8,
        max_pages_per_seq=64,
        max_model_len=512,
        prefill_chunk=64,
        interactive_slots=2,
        max_new_tokens=8,
        seed=0,
    ),
    gen_rows=8, gen_new=16, long_chars=100,
    cls_rows=4, cls_new=220,
    chat_new=30,
)
# largest |kernel - reference| / max|reference| accepted by the on-device
# kernel check; measured 0.016-0.020 on a v5e at qwen3-4b (PERF.md
# "Bring-up"), and a wrong kernel gives ~1.
KERNEL_REL_ERR_MAX = 0.1

SNIPPETS = [
    "battery life is incredible and it charges fast",
    "stopped working after two weeks, very disappointed",
    "decent value for the price but the build feels cheap",
    "exactly as described, shipping was quick",
    "the screen scratches way too easily",
    "customer support resolved my issue in minutes",
    "way too loud under load, returned it",
    "my kids love it, survived several drops already",
]
CLASSES = ["positive", "negative", "neutral"]
CHAT_SCHEMA = {
    "type": "json_schema",
    "json_schema": {
        "name": "answer",
        "schema": {
            "type": "object",
            "properties": {"answer": {"type": "string", "maxLength": 6}},
            "required": ["answer"],
        },
    },
}


class _TaggedStdout:
    """Prefix every line written to stdout (the SDK prints too)."""

    def __init__(self, out, tag: str):
        self._out, self._tag, self._bol = out, tag, True

    def write(self, text: str) -> int:
        for piece in text.splitlines(keepends=True):
            if self._bol:
                self._out.write(self._tag)
            self._out.write(piece)
            self._bol = piece.endswith("\n")
        return len(text)

    def __getattr__(self, name):
        return getattr(self._out, name)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def generate_prompts(n: int, long_chars: int) -> list:
    """Mixed lengths; every eighth prompt is longer than prefill_chunk."""
    rows = []
    for i in range(n):
        text = f"Review {i}: {SNIPPETS[i % len(SNIPPETS)]} (order #{1000 + i})"
        if i % 8 == 7:
            filler = " ".join(SNIPPETS)
            text = (text + " " + filler * 8)[:long_chars]
        elif i % 3 == 0:
            text = text + " " + SNIPPETS[(i + 3) % len(SNIPPETS)] * 2
        rows.append(text)
    return rows


def kernels_vs_reference(runner, ecfg) -> dict:
    """The three Pallas kernels against the jnp/XLA reference ON THIS
    DEVICE, at the model's full width and depth, on one small input
    (CPU tests only ever interpret them). Two fresh runners share the
    engine's weights and differ in ``use_pallas`` alone:

    - prefill of 200 tokens: last-position logits (flash prefill vs jnp
      attention) and the K/V pages it wrote (in-place KV write vs XLA
      scatter);
    - one decode step over those pages: logits of the paged decode
      kernel vs the gathered-pages reference.

    Returns the largest absolute error of each, relative to the
    reference's largest magnitude. bf16 rounding differs by path, so
    these are small, not zero; a wrong kernel decorrelates them (~1)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models import transformer

    MP = ecfg.max_pages_per_seq
    pal, ref = (
        ModelRunner(
            runner.mcfg, dataclasses.replace(ecfg, use_pallas=flag),
            params=runner.params, num_pages=1 + MP,
        )
        for flag in (runner.use_pallas, False)
    )
    n = 200
    ids = np.random.default_rng(0).integers(0, 256, n + 1).astype(np.int32)
    table = np.zeros((MP,), np.int32)
    n_pages = -(-(n + 1) // ecfg.kv_page_size)
    table[:n_pages] = np.arange(1, n_pages + 1)

    def rel_err(a, b) -> float:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / np.abs(b).max())

    out = {
        "prefill_logits": rel_err(
            pal.prefill(ids[:n], table), ref.prefill(ids[:n], table)
        )
    }
    pages = list(range(1, n_pages + 1))
    got, want = pal.read_pages(pages), ref.read_pages(pages)
    out["kv_pages"] = max(rel_err(got[k], want[k]) for k in ("k", "v"))

    def decode_logits(r):
        step = jax.jit(
            lambda params, cache: transformer.forward(
                r.mcfg, params, jnp.asarray(ids[None, n:]),
                jnp.full((1, 1), n, jnp.int32), jnp.ones((1,), jnp.int32),
                paged_past=r._paged(cache, jnp.asarray(table[None])),
                past_len=jnp.full((1,), n, jnp.int32),
                use_pallas=r.use_pallas, kernel_mesh=r.kernel_mesh,
            )[0][0, 0]
        )
        # both read the pages the PALLAS runner wrote
        return step(r.params, pal.cache)

    out["decode_logits"] = rel_err(decode_logits(pal), decode_logits(ref))
    for name, err in out.items():
        check(
            np.isfinite(err) and err < KERNEL_REL_ERR_MAX,
            f"kernel check {name}: relative error {err} vs the reference",
        )
    return out


def post_json(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Key chip-smoke"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def cache_entries(path) -> int:
    return sum(1 for _ in Path(path).iterdir()) if path else 0


def finished_job(client, engine, job_id: str, rows: int, url=None) -> dict:
    """Await the job over the client's transport, fetch its rows (over
    HTTP when ``url`` is given) and check them: SUCCEEDED, one output per
    input, no error row, a generated token on every row. Returns the
    facts, with the raw ``outputs`` for the caller's own checks."""
    check(bool(job_id), "job submission returned no id")
    done = client.await_job_completion(
        job_id, obtain_results=False, timeout=1100
    )
    status = client.get_job_status(job_id)
    check(
        done is not None and status == "SUCCEEDED",
        f"job {job_id} ended {status}: {client.get_job_failure_log(job_id)}",
    )
    if url:
        # the wire call itself: the SDK's results DataFrame drops the
        # per-row token counts and the error column
        res = post_json(
            f"{url}/job-results",
            {"job_id": job_id, "include_cumulative_logprobs": True},
        )["results"]
    else:
        res = engine.job_results(job_id, include_cumulative_logprobs=True)
    errors = [e for e in res.get("errors", []) if e]
    reasons = collections.Counter(
        engine.jobs.read_results(job_id)["finish_reason"].tolist()
    )
    tokens = res["gen_tokens"]
    check(len(res["outputs"]) == rows, f"{job_id}: row count")
    check(not errors, f"{job_id}: error rows {errors[:3]}")
    check(reasons.get("error", 0) == 0, f"{job_id}: {reasons}")
    check(
        len(tokens) == rows and min(tokens) > 0,
        f"{job_id}: a row generated no token {tokens}",
    )
    return {
        "job_id": job_id,
        "status": status,
        "rows": rows,
        "error_rows": 0,
        "finish_reasons": dict(reasons),
        "gen_tokens_min": min(tokens),
        "gen_tokens_total": sum(tokens),
        "outputs": res["outputs"],
    }


def drive_chats(remote, model: str, max_tokens: int, batch_running) -> dict:
    """Four sequential /v1/chat/completions requests while a batch job
    runs. The first warms the prefix store, so the streamed and the
    non-streamed request after it — same schema-constrained body,
    temperature 0 — see the same cached pages, take the same prefill and
    must produce the same content; the last is unconstrained."""
    ask = "Name one colour of the rainbow. Reply as JSON."
    constrained = dict(
        model=model, max_tokens=max_tokens, temperature=0.0,
        response_format=CHAT_SCHEMA,
    )
    chats, overlapped = {}, 0
    for kind in ("warm", "stream", "plain", "free"):
        during = batch_running()
        tokens = None
        if kind == "stream":
            chunks = list(remote.chat(ask, stream=True, **constrained))
            content = "".join(
                c["choices"][0]["delta"].get("content") or ""
                for c in chunks
            )
            finish = chunks[-1]["choices"][0]["finish_reason"]
        else:
            if kind == "free":
                resp = remote.chat(
                    "Say hello.", model=model, max_tokens=16, temperature=0.0
                )
            else:
                resp = remote.chat(ask, **constrained)
            content = resp["choices"][0]["message"]["content"]
            finish = resp["choices"][0]["finish_reason"]
            tokens = resp["usage"]["completion_tokens"]
            check(tokens > 0, f"chat {kind}: no completion tokens")
        overlapped += int(during and batch_running())
        check(finish in ("stop", "length"), f"chat {kind}: {finish}")
        chats[kind] = {"content": content, "finish_reason": finish,
                       "completion_tokens": tokens}
    check(
        chats["stream"]["content"] == chats["plain"]["content"],
        "streamed and non-streamed chat differ at temperature 0: "
        f"{chats['stream']['content']!r} vs {chats['plain']['content']!r}",
    )
    answer = json.loads(chats["plain"]["content"])
    check(
        isinstance(answer.get("answer"), str),
        f"chat content does not match its schema: {answer}",
    )
    check(overlapped >= 1, "no chat request overlapped the batch job")
    return {"answered": len(chats), "overlapped_batch": overlapped,
            "stream_equals_plain": True, "requests": chats}


def check_classify_rows(outputs: list, labels) -> None:
    """Every stored row parses against the template's schema, and the
    template's unpacked DataFrame says the same."""
    for i, out in enumerate(outputs):
        row = json.loads(out)  # raises on invalid JSON: a failure
        check(
            set(row) == {"scratchpad", "classification"}
            and isinstance(row["scratchpad"], str)
            and len(row["scratchpad"]) <= 400
            and row["classification"] in CLASSES,
            f"classify row {i} does not match its schema: {out!r}",
        )
    check(
        list(labels["classification"])
        == [json.loads(o)["classification"] for o in outputs],
        "template's unpacked labels differ from the stored rows",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="debug the script's control flow on a CPU with tiny-dense; "
        "every line it prints says so and it reports no device result",
    )
    rehearsal = ap.parse_args(argv).cpu_rehearsal
    if rehearsal:
        sys.stdout = _TaggedStdout(sys.stdout, REHEARSAL_TAG)
    plan = REHEARSAL if rehearsal else CHIP
    model, engine_key, cfg = plan["model"], plan["engine_key"], plan["config"]

    # hermetic state dir: no ~/.sutro/engine.json is read, nothing stays
    home = tempfile.mkdtemp(prefix="sutro-chip-smoke-")
    os.environ["SUTRO_HOME"] = home
    t_start = time.monotonic()

    import jax

    from sutro_tpu.engine.api import get_engine
    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.runner import device_report
    from sutro_tpu.ops import lowering
    from sutro_tpu.sdk import Sutro
    from sutro_tpu.server import start_server_thread

    # -- 1. the device, before anything is built on it -------------------
    ecfg = EngineConfig(**cfg)
    report = device_report(ecfg)
    print(json.dumps({"device_report": report}, indent=1), flush=True)
    platform = report["platform"]
    if platform != "tpu" and not rehearsal:
        raise SystemExit(
            f"chip_smoke: JAX found platform {platform!r} "
            f"({report['device_kind']}, {report['device_count']} device(s)), "
            "not a TPU. This check only runs on the chip."
        )
    if rehearsal:
        check(platform == "cpu", "--cpu-rehearsal is for a CPU backend")
    else:
        # built on demand from tracked sources (native/*.so is ignored)
        check(report["native_runtime"], "native runtime did not build/load")
        check(report["native_fsm"], "native FSM did not build/load")

    compile_s = collections.defaultdict(float)
    cache_events = collections.Counter()

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[str(kw.get("fun_name", "?"))] += secs

    def on_event(event: str, **kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            cache_events[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    cache_dir = report["compile_cache_dir"]
    cache_before = cache_entries(cache_dir)
    phases = {}

    # -- 2. the objects `sutro serve` builds -----------------------------
    t0 = time.monotonic()
    engine = get_engine(ecfg)
    server, _thread, url = start_server_thread(engine)
    phases["engine_and_daemon_s"] = time.monotonic() - t0
    try:
        # -- 3a. free generate over HTTP (SDK remote backend) ------------
        remote = Sutro(api_key="chip-smoke", base_url=url, backend="remote")
        prompts = generate_prompts(plan["gen_rows"], plan["long_chars"])
        check(
            max(map(len, prompts)) > cfg["prefill_chunk"],
            "no prompt longer than prefill_chunk",
        )
        gen_sampling = {"max_new_tokens": plan["gen_new"],
                        "temperature": 0.7}
        t0 = time.monotonic()
        job_a = remote.infer(
            prompts, model=model, stay_attached=False,
            sampling_params=gen_sampling, name="chip-smoke-generate",
        )
        # -- 3c. chat rides the same co-batched session while (a) runs ---
        chat = drive_chats(
            remote, model, plan["chat_new"],
            lambda: remote.get_job_status(job_a)
            in ("QUEUED", "STARTING", "RUNNING"),
        )
        phases["chat_http_s"] = time.monotonic() - t0
        gen_http = finished_job(
            remote, engine, job_a, plan["gen_rows"], url
        )
        phases["generate_http_s"] = time.monotonic() - t0

        # -- 3b. schema-constrained classify over HTTP -------------------
        reviews = [
            f"Review {i}: {SNIPPETS[i % len(SNIPPETS)]} "
            f"(order #{2000 + i})" + (" " + SNIPPETS[i % 5]) * (i % 4)
            for i in range(plan["cls_rows"])
        ]
        t0 = time.monotonic()
        labels = remote.classify(
            reviews, CLASSES, model=model, keep_scratchpad=True,
            name="chip-smoke-classify",
            sampling_params={"max_new_tokens": plan["cls_new"]},
        )
        check(labels is not None, "classify returned no results")
        job_b = next(
            j["job_id"] for j in remote.list_jobs()
            if j.get("name") == "chip-smoke-classify"
        )
        cls_http = finished_job(
            remote, engine, job_b, plan["cls_rows"], url
        )
        phases["classify_http_s"] = time.monotonic() - t0
        check_classify_rows(cls_http["outputs"], labels)

        # -- 3d. the same generate job through the in-process SDK --------
        local = Sutro(api_key="chip-smoke", engine_config=cfg)
        check(local.engine is engine, "SDK built a second engine")
        t0 = time.monotonic()
        job_c = local.infer(
            prompts, model=model, stay_attached=False,
            sampling_params=gen_sampling, name="chip-smoke-generate-sdk",
        )
        gen_sdk = finished_job(local, engine, job_c, plan["gen_rows"])
        phases["generate_sdk_s"] = time.monotonic() - t0

        # -- 4. which code actually ran, and is it right -----------------
        runner = engine._runner_cache[engine_key][0]
        info = runner.device_info()
        # counted BEFORE the kernel check below traces the kernels
        # itself: these are the paths the jobs above built
        kernels = lowering.snapshot()
        xla_decode = lowering.xla_decode_count()
        if not rehearsal:
            check(runner.use_pallas is True, runner.pallas_reason)
            for name, paths in kernels.items():
                check(
                    paths["lowered"] > 0 and paths["interpreted"] == 0,
                    f"kernel {name} was not lowered for the TPU: {paths}",
                )
        t0 = time.monotonic()
        kernel_errors = kernels_vs_reference(runner, ecfg)
        phases["kernels_vs_reference_s"] = time.monotonic() - t0
        stats = jax.devices()[0].memory_stats() or {}
    finally:
        server.shutdown()
        engine.close(timeout=30)
        shutil.rmtree(home, ignore_errors=True)
    phases["total_s"] = time.monotonic() - t_start

    for job in (gen_http, cls_http, gen_sdk):
        job.pop("outputs")
    mcfg = runner.mcfg
    doc = {
        "ok": True,
        "rehearsal": rehearsal,
        "device_report": report,
        "model": {
            "name": model, "engine_key": engine_key,
            "layers": mcfg.num_layers, "hidden": mcfg.hidden_size,
            "vocab": mcfg.vocab_size, "dtype": cfg["param_dtype"],
            "n_params": info["n_params"],
            "param_bytes": info["param_bytes"],
        },
        "engine_config": cfg,
        "pool": {
            "pages": info["pool_pages"], "bytes": info["pool_bytes"],
            "allocatable_pages": runner.alloc_pages,
            "worst_case_pages": 1 + cfg["decode_batch_size"]
            * cfg["max_pages_per_seq"],
        },
        "runner": {k: info[k] for k in (
            "n_devices", "host_devices", "mesh", "use_pallas",
            "pallas_reason",
        )},
        "jobs": {"generate_http": gen_http, "classify_http": cls_http,
                 "generate_sdk": gen_sdk},
        "chat": chat,
        "kernel_paths": kernels,
        "paged_decode_xla": xla_decode,
        "kernel_rel_err_vs_reference": kernel_errors,
        "compile_seconds": dict(
            sorted(compile_s.items(), key=lambda kv: -kv[1])
        ),
        "compile_seconds_total": sum(compile_s.values()),
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": cache_before,
            "entries_after": cache_entries(cache_dir),
            **cache_events,
        },
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "phase_seconds": {k: round(v, 2) for k, v in phases.items()},
    }
    text = json.dumps(doc, indent=1)
    if not rehearsal:
        out = REPO / "chiprun_out"
        out.mkdir(exist_ok=True)
        (out / "chip_smoke.json").write_text(text + "\n")
    print(text)
    print(
        json.dumps(
            {"ok": True, "device": {"platform": platform,
                                    "kind": report["device_kind"],
                                    "count": report["device_count"]}}
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
