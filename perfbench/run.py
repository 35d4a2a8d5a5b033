#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads BENCHMARK.json for the cell, its configuration file and its traffic
file (``perfbench/traffic/<traffic>.json``), builds the engine once, warms
every shape the window will use (set-up), checks the system's logits
against the plain reference, measures for ``--seconds``, drains, and
prints as its LAST line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with --trace 1).
With --trace 0 the metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics; a few seconds in the middle of the
window are traced with the JAX profiler.

It refuses to start (non-zero exit, no result line) unless JAX reports a
TPU with the chips the cell asks for. ``--cpu-rehearsal`` runs the same
control flow on the tiny cells of ``perfbench/rehearsal/cells.json``,
tags every line, and reports no device metric.

This file is driven by data: it names no model, no cell and no metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # as close to process start as Python allows

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import zlib  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import bytes_and_flops, correctness, trace_reduce  # noqa: E402
from perfbench.clientlog import ClientLog  # noqa: E402
from perfbench.reading import Reading  # noqa: E402

REHEARSAL_TAG = "[CPU REHEARSAL - not a device run] "
TRACE_SECONDS = 4.0  # unless the traffic file gives ``trace_seconds``


class Env:
    """What a generator is handed (see generators/__init__.py)."""

    def __init__(self, sut, cfg, seed: int, seconds: float, traffic_dir: Path,
                 overrides: Optional[Dict[str, Dict[str, Any]]] = None):
        self.sut, self.cfg, self.seed, self.seconds = sut, cfg, seed, seconds
        self.log = ClientLog()
        self._traffic_dir = traffic_dir
        self._overrides = overrides or {}

    def rng(self, name: str):
        import numpy as np

        return np.random.default_rng([int(self.seed), zlib.crc32(name.encode())])

    def load_traffic(self, name: str) -> Dict[str, Any]:
        traffic = json.loads((self._traffic_dir / f"{name}.json").read_text())
        traffic.update(self._overrides.get(name, {}))
        return traffic

    def build_generator(self, traffic: Dict[str, Any]):
        mod = importlib.import_module(
            "perfbench.generators." + traffic["generator"]
        )
        return mod.build(traffic, self)


def load_cell(bench: Dict[str, Any], workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"perfbench: no workload {workload!r}; have {sorted(cells)}"
        )
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((REPO / cfg_entry["file"]).read_text())
    return cell, cfg


def metrics_for(bench: Dict[str, Any], cell: Dict[str, Any], group: str):
    """The cell's metrics of one group (``end_to_end`` / ``per_layer``).
    A metric with a ``workloads`` key exists only in those cells; a
    per-layer metric without one exists in every cell that reports the
    end-to-end metric it ``moves``. A rehearsal cell says which real
    cell it ``stands_for``."""
    name = cell.get("stands_for", cell["name"])

    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if group == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench[group] if listed(m) and m["moves"] in reported]


def read_metrics(metrics: List[Dict[str, Any]], package: str, reading: Reading,
                 skip_sources=()) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        if m["source"] in skip_sources:
            continue
        mod = importlib.import_module(f"perfbench.{package}.{m['name']}")
        value = mod.read(reading)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Tracer:
    """A short profiler trace in the middle of the window, in a thread
    of its own; the Python tracer is off (it would record every call)."""

    def __init__(self, sut, start_at: float, seconds: float):
        self.sut, self.start_at, self.seconds = sut, start_at, seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.span = None
        self.spans = None
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _mark(self) -> float:
        import jax

        t = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME, mono_ns=t):
            pass
        return t * 1e-9

    def _run(self) -> None:
        import jax

        try:
            time.sleep(max(self.start_at - time.monotonic(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            lo = self._mark()
            time.sleep(self.seconds)
            hi = self._mark()
            self.span = (lo, hi)
            self.spans = self.sut.recorder_spans()
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported, never swallowed
            self.error = repr(e)

    def result(self) -> Optional[Dict[str, Any]]:
        self.thread.join(timeout=120.0)
        if self.error or self.span is None:
            raise RuntimeError(f"the profiler trace failed: {self.error}")
        files = sorted(Path(self.dir).glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        self.xplane = str(files[-1])
        return trace_reduce.load_xplane(self.xplane)


def measure(sut, cfg, traffic_name: str, traffic_dir: Path, seed: int,
            seconds: float, trace: bool, warm: bool = True,
            overrides=None, t_process: float = T_PROCESS):
    """Warm, check, run one window, drain. Returns (reading, env,
    number problems, number facts, raw trace or None)."""
    env = Env(sut, cfg, seed, seconds, traffic_dir, overrides)
    traffic = env.load_traffic(traffic_name)
    gen = env.build_generator(traffic)
    problems, facts = [], {}
    if warm:
        gen.warm()
        t_numbers = time.monotonic()
        problems, facts = correctness.numbers(sut, cfg, seed)
        # the check runs inside set-up: its share of it, and who
        # drove the decode it compared (``sut.logits_through_cache``)
        facts["numbers_seconds"] = time.monotonic() - t_numbers
        facts["numbers_source"] = getattr(sut, "numbers_source", "harness")
    t0 = time.monotonic() + gen.lead_in_s
    gen.start(t0)
    time.sleep(max(t0 - time.monotonic(), 0.0))
    startup_seconds = t0 - t_process
    reg0 = sut.registry()
    tracer = None
    if trace:
        # a cell whose device runs a program every few seconds asks for
        # a longer trace, so that no trace is empty; never over half
        span = min(float(traffic.get("trace_seconds", TRACE_SECONDS)),
                   seconds / 2.0)
        tracer = Tracer(sut, t0 + (seconds - span) / 2.0, span)
    t1 = t0 + seconds
    time.sleep(max(t1 - time.monotonic(), 0.0))
    reg1 = sut.registry()
    spans = window_spans = sut.recorder_spans()
    gen.stop(t1)
    raw = reduced = None
    trace_span = None
    if tracer is not None:
        raw = tracer.result()
        raw["xplane"] = tracer.xplane
        reduced = trace_reduce.reduce_trace(raw)
        trace_span, spans = tracer.span, tracer.spans
    reading = Reading(
        log=env.log, t0=t0, t1=t1, startup_seconds=startup_seconds, n_chips=sut.n_chips(),
        device_kind=sut.device_kind, cfg=cfg, traffic=traffic, reg0=reg0,
        reg1=reg1, spans=spans, compiles=list(sut.compiles),
        memory_peak_bytes=sut.memory_peak_bytes(), sut=sut, trace=reduced,
        trace_span=trace_span, window_spans=window_spans,
    )
    return reading, env, problems, facts, raw


def attempted_and_failed(reading: Reading):
    chats = reading.window_chats()
    jobs = [j for j in reading.log.jobs if not j["warm"]]
    failed_chats = [
        c for c in chats if c["error"] is not None or c["first"] is None
    ]
    failed_jobs = [
        j for j in jobs if j["status"] not in (None, "SUCCEEDED", "CANCELLED")
    ]
    return len(chats) + len(jobs), failed_chats, failed_jobs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="debug the control flow on a CPU with the tiny rehearsal "
        "cells; every line says so and no device metric is printed",
    )
    ap.add_argument(
        "--keep-trace", action="store_true",
        help="also write a description of the raw trace beside the run's JSON",
    )
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    tag = REHEARSAL_TAG if rehearsal else ""

    def say(text: str) -> None:
        for line in str(text).splitlines():
            print(tag + line, flush=True)

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    traffic_dir = HERE / "traffic"
    if rehearsal:
        cells = json.loads((HERE / "rehearsal" / "cells.json").read_text())
        bench = dict(
            bench, configs=cells["configs"], workloads=cells["workloads"],
            end_to_end=bench["end_to_end"] + cells.get("end_to_end", []),
            per_layer=bench["per_layer"] + cells.get("per_layer", []),
        )
        traffic_dir = HERE / "rehearsal" / "traffic"
    cell, cfg = load_cell(bench, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    from perfbench import sut as sut_mod

    try:
        sut = sut_mod.System(cfg, args.seed, rehearsal)
    except sut_mod.NoDevice as e:
        print(str(e), file=sys.stderr)
        return 3
    code = 0
    try:
        reading, env, num_problems, num_facts, raw = measure(
            sut, cfg, cell["traffic"], traffic_dir, args.seed, seconds,
            bool(args.trace),
        )
        acc_problems, acc_facts = correctness.accounting(env.log)
        attempted, failed_chats, failed_jobs = attempted_and_failed(reading)
        skip = ("device_trace",) if rehearsal else ()
        if args.trace:
            wanted = metrics_for(bench, cell, "per_layer")
            metrics = read_metrics(wanted, "layer_metrics", reading, skip)
            for m in wanted:
                if m["name"] not in metrics and m["source"] not in skip:
                    env.log.note(
                        f"no reading for per-layer metric {m['name']}: its "
                        "reader found nothing in this run, so the line "
                        "leaves it out"
                    )
        else:
            wanted = metrics_for(bench, cell, "end_to_end")
            metrics = read_metrics(wanted, "e2e_metrics", reading, skip)
            missing = [m["name"] for m in wanted if m["name"] not in metrics]
            if missing and not env.log.fatals:
                env.log.fatal(f"no reading for end-to-end metric(s) {missing}")
        device = {
            "platform": sut.platform, "kind": sut.device_kind,
            "count": sut.device_count,
            "memory_peak_bytes": reading.memory_peak_bytes,
        }
        breakdown = None
        if reading.trace is not None:
            if reading.trace["busy_s"] <= 0 and not rehearsal:
                env.log.fatal("the trace shows no operation on the device")
            if not rehearsal:
                device["busy_s"] = reading.trace["busy_s"]
                device["window_s"] = reading.trace["window_s"]
            breakdown = {
                "device_ops": trace_reduce.top_ops(reading.trace),
                "idle_gaps": trace_reduce.attribute_gaps(
                    reading.trace["gaps_ns"], trace_reduce.mono_offset_ns(raw),
                    [(s[0], s[1], s[2]) for s in reading.spans],
                ),
            }
        problems = num_problems + acc_problems
        result: Dict[str, Any] = {
            "correct": not problems,
            "attempted": attempted,
            "failed": len(failed_chats) + len(failed_jobs),
            "metrics": metrics,
            "device": device,
        }
        if breakdown is not None and not rehearsal:
            result["breakdown"] = breakdown
        # each number compared beside its limit: the line's last key
        result["compared"] = dict(
            correctness.compared(num_facts),
            accounting_problems=[len(acc_problems), 0],
        )
        facts = {
            "workload": cell["name"], "seed": args.seed, "seconds": seconds,
            "trace": args.trace, "n_chips": reading.n_chips,
            "numbers": num_facts, "accounting": acc_facts,
            # the configuration file's sizes describe the served model
            "params": {"from_shapes": bytes_and_flops.param_count(cfg),
                       "per_token": bytes_and_flops.active_param_count(cfg),
                       "served": sut.weight_count()},
            "compile_seconds_total": sum(c[2] for c in sut.compiles),
            "compiles": len(sut.compiles),
            "compiled_during_window": [
                [name, secs] for t, name, secs in sut.compiles
                if reading.t0 <= t <= reading.t1
            ],
            "jobs": len(env.log.jobs), "chats": len(env.log.chats),
            "modules": (reading.trace or {}).get("module_s"),
        }
        # for the run file only: when each job ran and each progress
        # update came, in seconds from the window's start
        timeline = {
            "jobs": [
                [j["submitted"] - reading.t0,
                 None if j["ended"] is None else j["ended"] - reading.t0,
                 j["status"], j["rows"], j["warm"]] for j in env.log.jobs
            ],
            "updates": [
                [t - reading.t0, n] for t, n in env.log.cumulative_tokens()
                if t >= reading.t0 - 1.0
            ][:400],
        }
        say(json.dumps({"facts": facts}, default=str))
        for note in env.log.notes:
            say(f"note: {note}")
        for c in failed_chats[:10]:
            say(f"failed: chat {c['trace_id']}: {c['error'] or 'no first token'}")
        for j in failed_jobs[:10]:
            say(f"failed: job {j['job_id']} ended {j['status']}")
        for p in problems[:40]:
            say(f"INCORRECT: {p}")
        write_run_file(cell, args, result, dict(facts, timeline=timeline), problems,
                       raw if args.keep_trace else None)
        if env.log.fatals:
            for f in env.log.fatals:
                print(f"perfbench: FATAL: {f}", file=sys.stderr)
            code = 4
    finally:
        sut.close()
    if code == 0:
        # the last lines of standard error, after whatever closing printed
        for name, (number, limit) in result["compared"].items():
            print(f"{tag}compared: {name} {number} limit {limit}",
                  file=sys.stderr, flush=True)
        say(json.dumps(result))
    return code


def write_run_file(cell, args, result, facts, problems, raw) -> None:
    """The run's JSON, under the git-ignored directory the chip tool
    brings back."""
    out = REPO / "chiprun_out" / "perfbench"
    try:
        out.mkdir(parents=True, exist_ok=True)
        stem = f"{cell['name']}.seed{args.seed}.trace{args.trace}"
        doc = {"result": result, "facts": facts, "problems": problems}
        (out / f"{stem}.json").write_text(json.dumps(doc, indent=1, default=str))
        if raw is not None:
            (out / f"{stem}.planes.json").write_text(json.dumps(
                trace_reduce.describe_xplane(raw["xplane"]), indent=1
            ))
            keep = {
                "sync": raw["sync"],
                "devices": {
                    k: {"ops": v["ops"][:4000], "modules": v["modules"][:400]}
                    for k, v in raw["devices"].items()
                },
            }
            (out / f"{stem}.trace.json").write_text(json.dumps(keep))
    except OSError as e:
        print(f"perfbench: could not write the run file: {e}", file=sys.stderr)


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the engine and of JAX may outlive main(); the run
    # started no other process, and this one ends here
    os._exit(_code)
