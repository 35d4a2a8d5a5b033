"""Bench-artifact trend report: BENCH_TREND.md (+ machine snapshot).

Reads every bench artifact the repo accumulates —

- ``BENCH_r*.json``   driver rounds: ``{n, cmd, rc, tail, parsed:
  {metric, value, unit, vs_baseline}}`` (a round whose ``unit`` is
  ``error`` or whose ``rc`` is non-zero carries no number);
- ``BENCH_E2E.json``  full-engine workloads: ``rows_per_hour``,
  ``tok_s_per_chip``, ``usd_per_1m_tokens`` per workload;
- ``BENCH_INTERACTIVE.json`` latency legs: TTFT/ITL p50/p99 idle vs
  co-batched, plus the retention grades

— and writes ``BENCH_TREND.md``: the round-by-round series, the
current graded metrics, and regressions whenever a graded metric moved
in the bad direction:

- between the two most recent *valid* driver rounds (always
  warn-only — rounds come from heterogeneous driver boxes), and
- between the current artifacts and the previous run's snapshot
  (``BENCH_TREND.json``, rewritten on every run so the comparison is
  always against the last time someone ran ``make bench-trend``).

Whether a cross-run regression **fails** or merely warns is decided by
measured variance, not by fiat (ROADMAP: "promote ``make bench-trend``
... once leg variance is characterized"). ``--characterize`` reruns
the cheap CPU legs (``bench_e2e.py``, ``bench_interactive.py``)
``CHARACTERIZE_RUNS`` times back-to-back on this box, computes each
graded metric's relative spread ((max-min)/median), and persists the
result in ``BENCH_TREND.json``:

- spread <= ``GATE_MAX_SPREAD`` -> the leg is *gated*: later runs FAIL
  (exit 1) when it regresses more than
  max(``GATE_FLOOR``, ``GATE_MARGIN`` x spread);
- noisier legs stay warn-only at ``TREND_TOLERANCE``, with the
  measured spread recorded in BENCH_TREND.md so the next
  characterization pass can revisit.

Until a characterization has been run, every leg is warn-only — the
gate is opt-in by measurement.

Direction matters: throughput-like metrics (rows/hour, tok/s,
retention) regress on drops; latency- and cost-like metrics (ttft/itl
seconds, $/1M tokens, ratio-vs-idle) regress on rises.

Usage: ``make bench-trend`` (or ``python benchmarks/bench_trend.py``);
``python benchmarks/bench_trend.py --characterize`` to (re)measure
variance and refresh the gate set.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
TREND_TOLERANCE = 0.15  # >15% move in the bad direction -> warning

# --characterize: rerun the cheap CPU legs this many times and grade
# each metric's relative spread ((max-min)/median). Legs whose spread
# is at/below GATE_MAX_SPREAD promote to a failing gate with a
# per-leg threshold of max(GATE_FLOOR, GATE_MARGIN x spread); the
# rest stay warn-only with the spread published in BENCH_TREND.md.
CHARACTERIZE_RUNS = 3
GATE_MAX_SPREAD = 0.05
GATE_FLOOR = 0.03
GATE_MARGIN = 3.0
# (script, extra env) — the producers behind the graded artifacts.
# Both are the CPU smoke variants the Makefile runs in CI.
CHEAP_LEGS = (
    ("bench_e2e.py", {"JAX_PLATFORMS": "cpu"}),
    ("bench_interactive.py", {"JAX_PLATFORMS": "cpu"}),
)
# artifacts the producers rewrite; characterization restores them so a
# variance pass never silently moves the repo's committed numbers
CHARACTERIZE_ARTIFACTS = ("BENCH_E2E.json", "BENCH_INTERACTIVE.json")

# graded metrics: (json-path, higher_is_better)
E2E_METRICS = (
    ("rows_per_hour", True),
    ("tok_s_per_chip", True),
    ("usd_per_1m_tokens", False),
    # rank_elo stage-graph tournament leg (bench_e2e.py): one-submit
    # DAG throughput and the prefix tokens it saves over the
    # client-side sequential loop. Warn-only unless a --characterize
    # run measures them stable enough to gate.
    ("server_rows_per_hour", True),
    ("server_prefill_tokens_saved", True),
)
INTERACTIVE_METRICS = (
    (("legs", "idle", "ttft_p99_s"), False),
    (("legs", "idle", "itl_p99_s"), False),
    (("legs", "cobatch", "ttft_p99_s"), False),
    (("legs", "cobatch", "itl_p99_s"), False),
    (("legs", "cobatch", "batch", "rows_per_hour"), True),
    (("legs", "grades", "ttft_p99_ratio_vs_idle"), False),
    (("legs", "grades", "batch_throughput_retention"), True),
    # warm-prefix serving legs (engine-lifetime radix prefix store):
    # warm must stay below cold, and the ratio must not creep up
    (("legs", "prefix_cold", "ttft_p99_s"), False),
    (("legs", "prefix_warm", "ttft_p99_s"), False),
    (("legs", "grades", "warm_prefix_ttft_p99_ratio"), False),
    # session hibernate/resume legs (tiered KV pool, SUTRO_KV_TIERS):
    # resuming a hibernated session must stay cheaper than its cold
    # prefill; warn-only until a characterization run gates them
    (("legs", "hibernate_resume", "cold_ttft_p99_s"), False),
    (("legs", "hibernate_resume", "resume_ttft_p99_s"), False),
    (("legs", "grades", "resume_ttft_p99_ratio_vs_cold"), False),
)
# replica-fleet legs (BENCH_FLEET.json, `make bench-fleet`): 3-replica
# batch scale-out and warm-prefix routing through the fleet router.
# Warn-only (not in CHEAP_LEGS, so never variance-gated): the hard
# fleet gates are tests/test_fleet.py + the --fleet op census.
FLEET_METRICS = (
    (("grades", "batch_speedup_3v1"), True),
    (("grades", "routed_prefix_hit_rate"), True),
    (("legs", "batch_1replica", "rows_per_s"), True),
    (("legs", "batch_3replica", "rows_per_s"), True),
)
# trace-replay legs (BENCH_REPLAY.json, `make bench-replay`): the
# recorded-arrival workload replayed through 1- and 3-replica fleets.
# Warn-only like the fleet legs: the hard obs gates are
# tests/test_fleet_obs.py + the --fleet-obs op census.
REPLAY_METRICS = (
    (("grades", "ttft_p99_1replica_s"), False),
    (("grades", "ttft_p99_3replica_s"), False),
    (("grades", "throughput_retention_3v1"), True),
    (("grades", "routed_prefix_hit_rate"), True),
    (("legs", "replay_1replica", "rps"), True),
    (("legs", "replay_3replica", "rps"), True),
)


def _load(path: Path):
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return None


def _dig(doc, path):
    cur = doc
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return cur if isinstance(cur, (int, float)) else None


def _moved_badly(prev: float, cur: float, higher_better: bool) -> bool:
    """True when cur regressed vs prev by more than the tolerance."""
    if prev is None or cur is None or prev == 0:
        return False
    delta = (cur - prev) / abs(prev)
    return (delta < -TREND_TOLERANCE) if higher_better else (
        delta > TREND_TOLERANCE
    )


def _pct(prev: float, cur: float) -> str:
    if not prev:
        return "n/a"
    return f"{(cur - prev) / abs(prev) * 100.0:+.1f}%"


def collect_rounds() -> list:
    rounds = []
    for p in sorted(glob.glob(str(REPO / "BENCH_r*.json"))):
        doc = _load(Path(p))
        if not isinstance(doc, dict):
            continue
        parsed = doc.get("parsed") or {}
        valid = (
            doc.get("rc") == 0
            and parsed.get("unit") not in (None, "error")
            and isinstance(parsed.get("value"), (int, float))
        )
        rounds.append({
            "file": os.path.basename(p),
            "n": doc.get("n"),
            "rc": doc.get("rc"),
            "valid": valid,
            "metric": parsed.get("metric"),
            "value": parsed.get("value") if valid else None,
            "unit": parsed.get("unit"),
            "vs_baseline": parsed.get("vs_baseline"),
        })
    rounds.sort(key=lambda r: (r["n"] is None, r["n"]))
    return rounds


def build_snapshot() -> dict:
    """Flat {metric-name: value} map of everything graded, for the
    next run's cross-run comparison."""
    snap: dict = {}
    e2e = _load(REPO / "BENCH_E2E.json")
    if isinstance(e2e, dict):
        for wl, rec in (e2e.get("workloads") or {}).items():
            if not isinstance(rec, dict):
                continue
            for key, _hb in E2E_METRICS:
                v = rec.get(key)
                if isinstance(v, (int, float)):
                    snap[f"e2e.{wl}.{key}"] = v
    inter = _load(REPO / "BENCH_INTERACTIVE.json")
    if isinstance(inter, dict):
        for path, _hb in INTERACTIVE_METRICS:
            v = _dig(inter, path)
            if v is not None:
                snap["interactive." + ".".join(path)] = v
    flt = _load(REPO / "BENCH_FLEET.json")
    if isinstance(flt, dict):
        for path, _hb in FLEET_METRICS:
            v = _dig(flt, path)
            if v is not None:
                snap["fleet." + ".".join(path)] = v
    rpl = _load(REPO / "BENCH_REPLAY.json")
    if isinstance(rpl, dict):
        for path, _hb in REPLAY_METRICS:
            v = _dig(rpl, path)
            if v is not None:
                snap["replay." + ".".join(path)] = v
    return snap


def _direction(name: str) -> bool:
    """higher_is_better for a snapshot key."""
    for key, hb in E2E_METRICS:
        if name.endswith("." + key):
            return hb
    for path, hb in INTERACTIVE_METRICS:
        if name == "interactive." + ".".join(path):
            return hb
    for path, hb in FLEET_METRICS:
        if name == "fleet." + ".".join(path):
            return hb
    for path, hb in REPLAY_METRICS:
        if name == "replay." + ".".join(path):
            return hb
    return True


def characterize() -> dict:
    """Rerun the cheap legs N times, measure per-metric spread, and
    return the variance map {metric: {samples, spread, gated,
    threshold}}. Restores the bench artifacts afterwards."""
    import subprocess

    backups = {
        name: (
            (REPO / name).read_bytes()
            if (REPO / name).exists() else None
        )
        for name in CHARACTERIZE_ARTIFACTS
    }
    pre = build_snapshot()
    samples: list = []
    try:
        for i in range(CHARACTERIZE_RUNS):
            for script, extra in CHEAP_LEGS:
                env = dict(os.environ)
                env.setdefault("JAX_PLATFORMS", "cpu")
                env.update(extra)
                proc = subprocess.run(
                    [sys.executable, str(REPO / script)],
                    cwd=REPO, env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                )
                if proc.returncode != 0:
                    tail = proc.stdout.decode(errors="replace")[-2000:]
                    raise RuntimeError(
                        f"characterize leg {script} failed "
                        f"(rc={proc.returncode}):\n{tail}"
                    )
            snap = build_snapshot()
            samples.append(snap)
            print(
                f"characterize run {i + 1}/{CHARACTERIZE_RUNS}: "
                f"{len(snap)} graded metrics", file=sys.stderr,
            )
    finally:
        for name, blob in backups.items():
            if blob is None:
                (REPO / name).unlink(missing_ok=True)
            else:
                (REPO / name).write_bytes(blob)

    variance: dict = {}
    for name in sorted(set().union(*[set(s) for s in samples])):
        vals = [s[name] for s in samples if name in s]
        if len(vals) < CHARACTERIZE_RUNS:
            continue  # flickering metric: disqualified from gating
        if all(v == pre.get(name) for v in vals):
            # never moved off the committed artifact value: this leg
            # was NOT remeasured by the rerun set (e.g. a workload
            # variant merged into BENCH_E2E.json by a separate
            # invocation) — a zero spread here is staleness, not
            # stability, so it must not promote to a gate
            continue
        vals.sort()
        med = vals[len(vals) // 2]
        if not med:
            continue
        spread = (vals[-1] - vals[0]) / abs(med)
        gated = spread <= GATE_MAX_SPREAD
        variance[name] = {
            "samples": [round(v, 6) for v in vals],
            "spread": round(spread, 4),
            "gated": gated,
            "threshold": round(
                max(GATE_FLOOR, GATE_MARGIN * spread), 4
            ) if gated else TREND_TOLERANCE,
        }
    return variance


def main() -> int:
    rounds = collect_rounds()
    snap = build_snapshot()
    prev_doc = _load(REPO / "BENCH_TREND.json") or {}
    prev_snap = prev_doc.get("snapshot") or {}
    if "--characterize" in sys.argv:
        variance = characterize()
    else:
        variance = prev_doc.get("variance") or {}
    warnings: list = []
    failures: list = []

    # round-over-round: the two most recent valid driver rounds
    valid_rounds = [r for r in rounds if r["valid"]]
    if len(valid_rounds) >= 2:
        a, b = valid_rounds[-2], valid_rounds[-1]
        if _moved_badly(a["value"], b["value"], True):
            warnings.append(
                f"driver round r{b['n']:02d} {b['metric']} = "
                f"{b['value']:.1f} {b['unit']} "
                f"({_pct(a['value'], b['value'])} vs r{a['n']:02d})"
            )

    # cross-run: current artifacts vs last snapshot. Gated legs
    # (variance-characterized as stable on this box) FAIL past their
    # per-leg threshold; everything else warns at TREND_TOLERANCE.
    for name, cur in sorted(snap.items()):
        prev = prev_snap.get(name)
        if prev is None or not prev:
            continue
        delta = (cur - prev) / abs(prev)
        bad = -delta if _direction(name) else delta
        leg = variance.get(name) or {}
        if leg.get("gated"):
            if bad > leg["threshold"]:
                failures.append(
                    f"{name}: {prev:.4g} -> {cur:.4g} "
                    f"({_pct(prev, cur)}; gate {leg['threshold']:.0%}, "
                    f"measured spread {leg['spread']:.1%})"
                )
        elif bad > TREND_TOLERANCE:
            warnings.append(
                f"{name}: {prev:.4g} -> {cur:.4g} ({_pct(prev, cur)})"
            )

    n_gated = sum(1 for v in variance.values() if v.get("gated"))
    lines = ["# Bench trend", ""]
    lines.append(
        f"Trend gate (`make bench-trend`): {n_gated} variance-"
        f"characterized legs fail past their per-leg threshold; the "
        f"rest warn past {TREND_TOLERANCE:.0%} in the bad direction. "
        "Compared against the previous run's `BENCH_TREND.json` "
        "snapshot and the prior driver round. Refresh the gate set "
        "with `python benchmarks/bench_trend.py --characterize` "
        f"(N={CHARACTERIZE_RUNS} reruns of the cheap CPU legs)."
    )
    lines.append("")
    if failures:
        lines.append(f"## Failures ({len(failures)})")
        lines.append("")
        for f in failures:
            lines.append(f"- ✗ {f}")
        lines.append("")
    if warnings:
        lines.append(f"## Warnings ({len(warnings)})")
        lines.append("")
        for w in warnings:
            lines.append(f"- ⚠ {w}")
    elif not failures:
        lines.append("## Warnings (0)")
        lines.append("")
        lines.append("- none — no graded metric moved "
                     f">{TREND_TOLERANCE:.0%} in the bad direction")
    lines.append("")

    if variance:
        lines.append(
            f"## Leg variance (N={CHARACTERIZE_RUNS} back-to-back "
            "reruns)"
        )
        lines.append("")
        lines.append(
            "| metric | spread | class | threshold |"
        )
        lines.append("|---|---|---|---|")
        for name, v in sorted(variance.items()):
            cls = "**gate**" if v.get("gated") else "warn-only"
            lines.append(
                f"| {name} | {v['spread']:.1%} | {cls} | "
                f"{v['threshold']:.0%} |"
            )
        lines.append("")
        # graded metrics the characterization run predates have no
        # measured spread yet — they stay warn-only at the default
        # tolerance until the next `--characterize` refresh
        uncharacterized = sorted(
            name for name in snap if name not in variance
        )
        if uncharacterized:
            lines.append(
                "Not yet characterized (warn-only at "
                f"{TREND_TOLERANCE:.0%} until the next "
                "`--characterize` run measures their spread): "
                + ", ".join(f"`{n}`" for n in uncharacterized)
            )
            lines.append("")

    lines.append("## Driver rounds (BENCH_r*.json)")
    lines.append("")
    lines.append("| round | status | metric | value | unit | vs baseline |")
    lines.append("|---|---|---|---|---|---|")
    for r in rounds:
        status = "ok" if r["valid"] else f"error (rc={r['rc']})"
        value = f"{r['value']:.1f}" if r["valid"] else "—"
        metric = (r["metric"] or "—")
        if len(metric) > 48:
            metric = metric[:45] + "..."
        lines.append(
            f"| r{r['n']:02d} | {status} | {metric} | {value} | "
            f"{r['unit'] or '—'} | {r['vs_baseline'] if r['valid'] else '—'} |"
        )
    if not rounds:
        lines.append("| — | no rounds found | | | | |")
    lines.append("")

    lines.append("## Current graded metrics")
    lines.append("")
    lines.append("| metric | value | prev | delta | direction |")
    lines.append("|---|---|---|---|---|")
    for name, cur in sorted(snap.items()):
        prev = prev_snap.get(name)
        hb = _direction(name)
        delta = _pct(prev, cur) if prev is not None else "—"
        prev_s = f"{prev:.4g}" if prev is not None else "—"
        lines.append(
            f"| {name} | {cur:.4g} | {prev_s} | {delta} | "
            f"{'↑ better' if hb else '↓ better'} |"
        )
    if not snap:
        lines.append("| — | no artifacts found | | | |")
    lines.append("")

    (REPO / "BENCH_TREND.md").write_text("\n".join(lines) + "\n")
    (REPO / "BENCH_TREND.json").write_text(json.dumps({
        "tolerance": TREND_TOLERANCE,
        "snapshot": snap,
        "variance": variance,
        "warnings": warnings,
        "failures": failures,
    }, indent=2) + "\n")

    for w in warnings:
        print(f"WARN: {w}", file=sys.stderr)
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    print(json.dumps({
        "rounds": len(rounds),
        "graded_metrics": len(snap),
        "gated_legs": n_gated,
        "warnings": len(warnings),
        "failures": len(failures),
        "report": "BENCH_TREND.md",
    }))
    # noisy legs warn and never block; variance-characterized gates do
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
