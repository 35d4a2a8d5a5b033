# Top-level developer/CI entry points (reference analogue: Makefile +
# .github/monorepo-ci.sh, which only compile-checked; this one actually
# builds the native runtime and runs the suite).

PY ?= python

.PHONY: all native test test-oneshot test-fast compile-check lint lint-baseline \
	lint-schema chaos telemetry-check monitor-check control-check \
	prefix-check tier-check fleet-check fleet-obs-check graph-check dryrun \
	chip-smoke golden host-profile clean

all: native compile-check

native:
	$(MAKE) -C native

# full suite (CPU, 8 virtual devices via tests/conftest.py), run
# per-file with crash-only retries: this build host's XLA:CPU compiler
# segfaults rarely but nondeterministically inside
# backend_compile_and_load under load (observed twice, different test
# files, both pass in isolation) — a single-process run can die at ~60%
# through no fault of the code. Real test failures still fail fast.
test: native
	bash .github/run_tests_chunked.sh

# single-process run (faster when the host's XLA CPU compiler is
# healthy; see `test` for why the chunked runner is the default)
test-oneshot: native
	$(PY) -m pytest tests/ -q

# quick gate: everything except the slow multi-device / golden suites
test-fast: native
	$(PY) -m pytest tests/ -q -x \
		--ignore=tests/test_pipeline.py \
		--ignore=tests/test_golden.py \
		--ignore=tests/test_parallel.py \
		--ignore=tests/test_ring.py

# the reference CI ran `python -m compileall` only (SURVEY §4); kept as
# the cheapest smoke layer
compile-check:
	$(PY) -m compileall -q sutro_tpu tests chip_smoke.py

# graftlint: engine-aware static analysis (lock discipline, jit purity,
# thread/exception hygiene) gated against the committed baseline —
# non-zero exit on any NEW finding (README "Static analysis")
# wall-time budget: the whole-tree scan (all passes, including the
# inter-procedural data-race walk) must stay under 60s to hold its
# place as a tier-1 gate
lint:
	timeout -k 5 60 $(PY) -m sutro_tpu.analysis sutro_tpu

# accept the current findings as the new baseline (review the diff of
# sutro_tpu/analysis/baseline.json before committing!)
lint-baseline:
	$(PY) -m sutro_tpu.analysis sutro_tpu --write-baseline

# regenerate the dp/elastic wire-frame schema from the senders and fail
# if the committed analysis/wire_schema.json drifted (CI runs this: a
# frame/key change must land WITH its schema update — removals are then
# caught by the wire-key-removed lint pass)
lint-schema:
	$(PY) -m sutro_tpu.analysis sutro_tpu --write-wire-schema
	git diff --exit-code -- sutro_tpu/analysis/wire_schema.json

# seeded chaos suite (FAILURES.md): deterministic fault injection
# end-to-end — row quarantine (incl. the 256-row poison-row acceptance
# case), transient I/O retry, torn chunks, device errors + resume
# bit-identity, crash-mid-finalize, dp liveness, plus the elastic
# fleet gate (worker crash/hang/mid-frame drop, SIGTERM preemption
# drain, late join, steal race, coordinator crash + resume), plus the
# replica-fleet chaos/degradation subset (replica kill mid-job with
# bit-identical failover, mid-stream crash -> structured error,
# old/new protocol skew -> probe-only routing). A tier-1 CI step.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py tests/test_elastic.py \
		-q -m "not slow" -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py -q -m "not slow" \
		-p no:cacheprovider -k "chaos or degradation"

# telemetry gate (OBSERVABILITY.md): exporter golden-file + flight-
# recorder/reconciliation tests + distributed telemetry (trace
# propagation, federation, doctor golden) + tail-latency forensics
# (exemplars, request traces, Perfetto export golden), then the
# telemetry-on vs telemetry-off host-overhead comparison (< 2% delta
# asserted in code, including the dp-coordinator wire leg and the
# exemplars-on forensics census). Tier-1 CI.
telemetry-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_telemetry.py \
		tests/test_distributed_telemetry.py tests/test_traces.py \
		-q -m "not slow" -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --telemetry

# live-monitor gate (OBSERVABILITY.md "Live monitor"): SLO rule
# hysteresis/debounce, windowed percentiles, streaming doctor verdicts,
# tenant attribution + the monitor tick-cost leg (budget asserted in
# code; zero sampling work with telemetry off). Tier-1 CI.
monitor-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_monitor.py \
		-q -m "not slow" -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --monitor

# enforcement gate (OBSERVABILITY.md "Enforcement"): token-bucket
# admission, priority-ladder policy, autotuner hysteresis, controller
# degradation-to-pass-through, and the control-on/off host-overhead
# budget (zero-cost when SUTRO_CONTROL=0, asserted in code). Tier-1 CI.
control-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_control.py \
		tests/test_chaos.py -k "control" -q -m "not slow" \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --control

# prefix-store gate (OBSERVABILITY.md "Prefix store"): radix-tree
# units (LRU order, pin refcounts, racer declines), scheduler
# integration (second identical-template job prefills the tail only,
# bit-identical to SUTRO_PREFIX_STORE=0), eviction-vs-admission and
# lookup-fault chaos, and the engine close()/page-conservation
# contract. Tier-1 CI.
prefix-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_prefix_store.py \
		-q -m "not slow" -p no:cacheprovider

# tiered-KV gate (OBSERVABILITY.md "KV tiers"): pool units (quantized
# payload parity, host LRU + disk spill, pinned hibernated rows),
# scheduler integration (demote->promote and hibernate->resume
# bit-identical on the int8 pool, SUTRO_KV_TIERS=0 bit-identical with
# a zero op census), tier-hop chaos (torn demote/promote/disk-write),
# exact page conservation, and the sticky-session chat checkpoint/
# resume path over the live gateway. Tier-1 CI.
tier-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_kv_tiers.py \
		-q -m "not slow" -p no:cacheprovider

# replica-fleet gate (FAILURES.md "Replica fleet"): breaker state
# machine + bounded backoff + flap detection, health-checked routing
# (warm-prefix affinity, least-loaded, drain exclusion), batch-job
# failover over the shared jobstore (zero rows lost or duplicated,
# bit-identical at temperature 0), mid-stream structured errors,
# protocol-skew degradation, SDK reconnect-with-cursor — then the
# --fleet op census (per-request routing decision under the same 2%
# host-overhead envelope as telemetry; zero ops when off). Tier-1 CI.
fleet-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py \
		-q -m "not slow" -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --fleet

# fleet-observability gate (OBSERVABILITY.md "Fleet observability"):
# cross-replica trace propagation (X-Sutro-Trace forward + adoption,
# stitched GET /trace/{id} with per-process lanes pinned by golden
# export), federated /metrics under the replica label with the _fleet
# aggregate + route-latency exemplars, fleet monitor SLO rules firing
# AND resolving under live chaos, protocol skew both directions, the
# replay capture/load round-trip — then the --fleet-obs op census
# (per-request trace+exemplar cost under the same 2% host-overhead
# envelope; zero ops and zero federation sends when telemetry off).
# Tier-1 CI.
fleet-obs-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet_obs.py \
		-q -m "not slow" -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --fleet-obs

# stage-graph gate (README "Stage graphs"): submit-time DAG validation
# (structured INVALID_GRAPH through API + SDK), generate->score->rank
# bit-identity vs the client-side job sequence at temp 0, streaming
# inter-stage admission (downstream first result before upstream done,
# asserted via stage spans), per-stage quarantine propagation, DAG
# crash/resume chaos (only missing stage chunks replayed), the elo
# tie-break pin, and the --stagegraph zero-overhead op census for
# stage-less jobs. Tier-1 CI.
graph-check:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_stagegraph.py \
		tests/test_evals.py -q -m "not slow" -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --stagegraph

# multi-chip sharding dry run on 8 virtual CPU devices
dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# the quickest proof that the system still starts on the chip: one
# process, qwen3-4b through LocalEngine + the HTTP daemon on a TPU
# (exits non-zero without one; README "Tests & benchmarks")
chip-smoke:
	$(PY) chip_smoke.py

# host-side overhead profile (stub runner, no chip): per-window micro
# legs + full-job-lifecycle e2e legs at 512/20k rows, with the
# pipelined-decode budget (host_ms_per_window <= window_ms x
# (lookahead-1)) and flat-scaling (20k <= 1.25x 512 per-row) asserted
# in code — non-zero exit on regression
host-profile:
	JAX_PLATFORMS=cpu $(PY) benchmarks/profile_host_overhead.py --e2e

# README 3-row quickstart on real trained weights -> GOLDEN.json
golden:
	$(PY) benchmarks/golden_quickstart.py

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
