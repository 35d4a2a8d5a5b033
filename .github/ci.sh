#!/usr/bin/env bash
# CI entry point: build native helpers, compile-check, run the suite on
# CPU with 8 virtual devices. (The reference's CI was compileall only —
# .github/monorepo-ci.sh in /root/reference; SURVEY §4 calls for better.)
set -euo pipefail
cd "$(dirname "$0")/.."
make native
make compile-check
# tier-1 gate: graftlint static analysis vs the committed baseline —
# any new lock-discipline / jit-purity / hygiene / resource-lifecycle /
# kill-switch / wire-protocol / cardinality finding fails CI
make lint
# code-scanning artifact: the same findings as SARIF 2.1.0 for upload
# (warn-only — `make lint` above is the gate)
python -m sutro_tpu.analysis sutro_tpu --no-baseline --format sarif \
    > graftlint.sarif || true
# tier-1 gate: the committed wire-frame schema must match what the
# dp/elastic senders actually produce
make lint-schema
# tier-1 gate: seeded chaos subset — deterministic fault injection must
# keep reaching terminal states with partial-store consistency
make chaos
# tier-1 gate: telemetry — exporter golden file, flight-recorder
# reconciliation, and the telemetry-on/off host-overhead budget
make telemetry-check
# tier-1 gate: live monitor — SLO hysteresis/debounce, streaming doctor
# verdicts, tenant attribution, and the monitor tick-cost budget
# (zero sampling work with telemetry off, asserted in code)
make monitor-check
# tier-1 gate: enforcement control plane — tenant admission buckets,
# priority-ladder preemption, autotuner hysteresis, degradation to
# pass-through under injected controller faults, and the control-on/off
# host-overhead budget (zero cost with SUTRO_CONTROL=0)
make control-check
# tier-1 gate: cross-job radix prefix store — repeat-template jobs must
# prefill only the novel tail, bit-identically to the store-off engine,
# with exact page conservation under eviction pressure and lookup
# faults degrading to plain misses
make prefix-check
# tier-1 gate: tiered paged-KV pool + session hibernation — demote/
# promote and hibernate/resume must be bit-identical on the int8 pool,
# SUTRO_KV_TIERS=0 must be bit-identical with a zero tier-op census,
# and torn migrations (demote/promote/disk-write) must never corrupt
# or lose a row
make tier-check
# tier-1 gate: replica fleet front door — breaker discipline, health-
# checked routing with warm-prefix affinity, batch-job failover with
# zero rows lost or duplicated (bit-identical at temperature 0),
# mid-stream structured errors instead of silent hangs, protocol-skew
# degradation to probe-only routing, and the per-request routing-
# decision host budget (zero telemetry ops when off)
make fleet-check
# tier-1 gate: fleet observability plane — cross-replica trace
# stitching (X-Sutro-Trace propagation, golden Perfetto export, no
# negative gaps after skew re-anchoring), federated /metrics under the
# replica label with the _fleet aggregate and exemplar trace ids, the
# fleet monitor firing AND resolving stock SLO rules under live chaos,
# protocol skew in both directions, the replay JSONL round-trip, and
# the --fleet-obs census (zero obs ops and zero federation sends with
# SUTRO_TELEMETRY=0)
make fleet-obs-check
# tier-1 gate: server-side stage graphs — DAG validation (structured
# INVALID_GRAPH 400), generate->score->rank bit-identity vs the
# client-side sequence at temp 0, streaming inter-stage admission,
# per-stage quarantine, crash/resume replaying only missing stage
# chunks, and the zero-overhead census for stage-less jobs
make graph-check
bash .github/run_tests_chunked.sh
