"""Engine telemetry: metrics registry, span tracing, flight recorder.

The operator surface the hosted reference keeps server-side (SURVEY §0:
progress accounting and quota enforcement live behind api.sutro.sh),
rebuilt for the TPU-native engine. Three pillars:

1. **Metrics registry** (:mod:`.registry`) — lock-light counters,
   gauges and bounded histograms with thread-local write shards, fixed
   label cardinality, and Prometheus-text / JSON exporters. Scraped via
   ``GET /metrics`` on the engine daemon (server.py) or ``sutro
   telemetry`` on the CLI.
2. **Span tracer + flight recorder** (:mod:`.spans`) — per-stage
   timings (tokenize, constraint compile, prefill, decode window,
   accept, flush, finalize, dp round) in a bounded ring buffer, dumped
   to ``$SUTRO_HOME/jobs/<job_id>/telemetry.json`` when a job FAILs
   (pairing with the job record's ``failure_log[]``) and on demand.
3. **Per-job counters** — exact rows/tokens accumulators outside the
   label space (job ids are unbounded), reconciled against job results.

The catalog of engine metrics lives here (OBSERVABILITY.md documents
names/labels/units). Everything is guarded by one module-global switch:
``SUTRO_TELEMETRY=0`` (or :func:`set_enabled`) turns instrumentation
off, and call sites pay a single attribute load + truth test — the
same zero-overhead-when-off pattern as engine/faults.py ``ACTIVE``.
"""

from __future__ import annotations

import json
import logging
import os
import time
from pathlib import Path
from typing import Any, Dict, Optional

from .registry import DEFAULT_BUCKETS, MetricsRegistry
from .spans import (
    DEFAULT_CAPACITY,
    FlightRecorder,
    JobCounters,
    JobTelemetryStore,
)
from .traces import DEFAULT_TRACE_CAPACITY, TraceStore

logger = logging.getLogger(__name__)

__all__ = [
    "REGISTRY",
    "RECORDER",
    "JOBS",
    "TRACES",
    "distributed",
    "monitor",
    "traces",
    "traceexport",
    "enabled",
    "set_enabled",
    "stage_observe",
    "job",
    "job_doc",
    "dump_job",
    "load_job_dump",
    "MetricsRegistry",
    "FlightRecorder",
    "JobCounters",
    "JobTelemetryStore",
    "TraceStore",
]

# -- the one enable switch ---------------------------------------------

ENABLED: bool = os.environ.get("SUTRO_TELEMETRY", "1").lower() not in (
    "0", "false", "off",
)


def enabled() -> bool:
    return ENABLED


def set_enabled(on: bool) -> bool:
    """Flip instrumentation globally (tests / the overhead profiler).
    Components that latch the switch at construction (the scheduler's
    timer sink) pick it up on their next construction."""
    global ENABLED
    ENABLED = bool(on)
    return ENABLED


# -- singletons --------------------------------------------------------

REGISTRY = MetricsRegistry()
RECORDER = FlightRecorder(
    capacity=int(os.environ.get("SUTRO_TELEMETRY_SPANS", DEFAULT_CAPACITY))
)
JOBS = JobTelemetryStore(
    capacity=int(os.environ.get("SUTRO_TELEMETRY_JOBS", 256))
)
TRACES = TraceStore(
    capacity=int(
        os.environ.get("SUTRO_TELEMETRY_TRACES", DEFAULT_TRACE_CAPACITY)
    )
)

# -- engine metric catalog (documented in OBSERVABILITY.md) ------------

STAGE_SECONDS = REGISTRY.histogram(
    "sutro_stage_seconds",
    "Engine stage latency: every name of STAGES below (the engine's "
    "stages and the scheduler thread's phases)",
    labels=("stage",),
    unit="seconds",
    max_series=32,
)
ROWS_TOTAL = REGISTRY.counter(
    "sutro_rows_total",
    "Result rows emitted by terminal outcome",
    labels=("outcome",),  # ok | quarantined | cancelled
    max_series=8,
)
TOKENS_TOTAL = REGISTRY.counter(
    "sutro_tokens_total",
    "Tokens processed by direction (accounted at job finalize)",
    labels=("direction",),  # in | out
    unit="tokens",
)
JOBS_TOTAL = REGISTRY.counter(
    "sutro_jobs_total",
    "Jobs reaching a terminal status",
    labels=("status",),  # succeeded | failed | cancelled
    max_series=8,
)
ROW_EVENTS_TOTAL = REGISTRY.counter(
    "sutro_failure_events_total",
    "failure_log[] events appended (row_retry, row_quarantined, "
    "io_retry, torn_chunk_quarantined, job_failed, ...)",
    labels=("event",),
    max_series=16,
)
FAULTS_INJECTED_TOTAL = REGISTRY.counter(
    "sutro_faults_injected_total",
    "Deterministic fault-plan injections fired, by site",
    labels=("site",),
    max_series=32,
)
IO_RETRIES_TOTAL = REGISTRY.counter(
    "sutro_io_retries_total",
    "Transient-I/O retry attempts (engine/faults.retry_transient)",
    labels=("what",),
    max_series=16,
)
TOKENIZE_ROWS_TOTAL = REGISTRY.counter(
    "sutro_tokenize_rows_total",
    "Prompt rows tokenized through encode_chat_batch",
    unit="rows",
)
DP_EVENTS_TOTAL = REGISTRY.counter(
    "sutro_dp_events_total",
    "Data-parallel coordinator events",
    # reconnect | stall | fault_forwarded | reject | join | requeue |
    # reshard | steal | drain | dup_result | resume_port_busy
    labels=("kind",),
    max_series=16,
)
DP_FLEET_SIZE = REGISTRY.gauge(
    "sutro_dp_fleet_size",
    "Live dp ranks (running or idle-parked) in the coordinator's "
    "current elastic round, coordinator included",
    unit="ranks",
)
DP_REQUEUED_ROWS_TOTAL = REGISTRY.counter(
    "sutro_dp_requeued_rows_total",
    "Rows returned to the pending pool after a rank died, stalled, "
    "tore a frame, drained (preemption), or never connected",
    unit="rows",
)
DP_STOLEN_ROWS_TOTAL = REGISTRY.counter(
    "sutro_dp_stolen_rows_total",
    "Straggler tail rows dual-assigned to an idle rank "
    "(first result wins; duplicates dropped by row id)",
    unit="rows",
)
TOKENS_PER_SECOND = REGISTRY.gauge(
    "sutro_tokens_per_second",
    "Most recent total token throughput reported by a running job",
    unit="tokens/s",
)
ROWS_PER_SECOND = REGISTRY.gauge(
    "sutro_rows_per_second",
    "Most recent row completion rate by workload "
    "(generate, embed, dp, interactive — dp is the coordinator's "
    "pod-merged rate; interactive is the serving tier's request rate)",
    labels=("workload",),
    unit="rows/s",
    max_series=8,
)
# -- interactive serving tier (serving/gateway.py, OBSERVABILITY.md) ----
TTFT_SECONDS = REGISTRY.histogram(
    "sutro_interactive_ttft_seconds",
    "Interactive request time-to-first-token (admission wait + prefill "
    "+ first decode), measured from gateway submit",
    unit="seconds",
)
ITL_SECONDS = REGISTRY.histogram(
    "sutro_interactive_itl_seconds",
    "Interactive inter-token latency (gap between consecutive streamed "
    "tokens of one request)",
    unit="seconds",
)
INTERACTIVE_REQUESTS_TOTAL = REGISTRY.counter(
    "sutro_interactive_requests_total",
    "Interactive serving requests by terminal outcome",
    labels=("outcome",),  # ok | cancelled | error | rejected
    max_series=8,
)
INTERACTIVE_ACTIVE = REGISTRY.gauge(
    "sutro_interactive_active",
    "Interactive requests currently admitted or streaming",
)
INTERACTIVE_PREEMPTIONS_TOTAL = REGISTRY.counter(
    "sutro_interactive_preemptions_total",
    "Batch rows suspended to admit an interactive request inside the "
    "interactive_slots budget (the row re-admits row-granularly)",
)
TOKENS_PER_SECOND_PER_CHIP = REGISTRY.gauge(
    "sutro_tokens_per_second_per_chip",
    "Most recent per-chip token throughput (Throughput estimator)",
    unit="tokens/s",
)
JOBS_RUNNING = REGISTRY.gauge(
    "sutro_jobs_running",
    "Generation/embedding jobs currently executing in this process",
)
SPANS_DROPPED = REGISTRY.gauge(
    "sutro_flight_recorder_dropped",
    "Spans evicted from the flight-recorder ring since process start",
)
# -- tenant attribution + live monitor (telemetry/monitor.py) -----------
# Tenant series ride the registry's ordinary cardinality admission: the
# tenant label value space is capped at TENANT_MAX_SERIES and overflow
# collapses into the standard ("_overflow", ...) series — an abusive
# tenant-id generator cannot grow the scrape unboundedly.
TENANT_MAX_SERIES = int(os.environ.get("SUTRO_TENANT_MAX_SERIES", 32))
TENANT_REQUESTS_TOTAL = REGISTRY.counter(
    "sutro_tenant_requests_total",
    "Submissions by tenant and kind (batch job submits and interactive "
    "requests)",
    labels=("tenant", "kind"),  # kind: batch | interactive
    max_series=TENANT_MAX_SERIES,
)
TENANT_ROWS_TOTAL = REGISTRY.counter(
    "sutro_tenant_rows_total",
    "Result rows attributed to a tenant at job terminal status",
    labels=("tenant", "outcome"),  # ok | quarantined
    max_series=TENANT_MAX_SERIES,
    unit="rows",
)
TENANT_TOKENS_TOTAL = REGISTRY.counter(
    "sutro_tenant_tokens_total",
    "Tokens attributed to a tenant at job terminal status",
    labels=("tenant", "direction"),  # in | out
    max_series=TENANT_MAX_SERIES,
    unit="tokens",
)
ALERTS_TOTAL = REGISTRY.counter(
    "sutro_monitor_alerts_total",
    "SLO alert lifecycle transitions emitted by the live monitor",
    labels=("rule", "state"),  # state: firing | resolved
    max_series=32,
)
ADMISSION_REJECTIONS_TOTAL = REGISTRY.counter(
    "sutro_admission_rejections_total",
    "Submits rejected by the control plane's per-tenant token buckets",
    labels=("tenant",),
    max_series=TENANT_MAX_SERIES,
)
PREEMPTIONS_TOTAL = REGISTRY.counter(
    "sutro_preemptions_total",
    "Decode rows suspended by the priority ladder "
    "(labels are the preemptor's and victim's job_priority)",
    labels=("from", "to"),
    unit="rows",
    max_series=32,
)
AUTOTUNE_ADJUSTMENTS_TOTAL = REGISTRY.counter(
    "sutro_autotune_adjustments_total",
    "Live engine-config adjustments applied by the control-plane "
    "autotuner",
    labels=("knob",),
    max_series=16,
)
PREFIX_STORE_HITS_TOTAL = REGISTRY.counter(
    "sutro_prefix_store_hits_total",
    "Radix prefix-store lookups that matched at least one KV page",
)
PREFIX_STORE_MISSES_TOTAL = REGISTRY.counter(
    "sutro_prefix_store_misses_total",
    "Radix prefix-store lookups that matched nothing",
)
PREFIX_STORE_EVICTIONS_TOTAL = REGISTRY.counter(
    "sutro_prefix_store_evictions_total",
    "Unpinned prefix-store pages evicted under allocation pressure",
    unit="pages",
)
PREFIX_STORE_TOKENS_SAVED_TOTAL = REGISTRY.counter(
    "sutro_prefix_store_prefill_tokens_saved_total",
    "Prefill tokens skipped because their KV was already resident in "
    "the prefix store",
    unit="tokens",
)
# -- tiered paged-KV pool (engine/kvtier.py, OBSERVABILITY.md) ----------
KV_TIER_PAGES = REGISTRY.gauge(
    "sutro_kv_tier_pages",
    "KV pages resident per below-HBM tier (host = int8 page payloads "
    "in pinned RAM, disk = npz bundles under sutro_home()/kvtier)",
    labels=("tier",),  # host | disk
    unit="pages",
    max_series=4,
)
KV_MIGRATIONS_TOTAL = REGISTRY.counter(
    "sutro_kv_migrations_total",
    "Tier-hop page migrations by direction (demote = device->host, "
    "promote = host/disk->device, disk_write/disk_read = host<->disk)",
    labels=("dir",),  # demote | promote | disk_write | disk_read
    max_series=8,
)
MOE_ROUTED_ROWS_TOTAL = REGISTRY.counter(
    "sutro_moe_routed_rows_total",
    "Row-expert pairs the routed layers' grouped products computed, "
    "summed over routed layers (counted on the device inside each "
    "dispatch, fetched with its tokens)",
    unit="rows",
)
MOE_ROWS_ELSEWHERE_TOTAL = REGISTRY.counter(
    "sutro_moe_rows_elsewhere_total",
    "Row-expert pairs the routers sent to experts this chip does not "
    "hold (a model told its share of each layer's experts: another "
    "chip's work, left out here), summed over routed layers; beside "
    "sutro_moe_routed_rows_total, which counts the pairs computed",
    unit="rows",
)
PREFILL_TOKENS_TOTAL = REGISTRY.counter(
    "sutro_prefill_tokens_total",
    "Token positions the prefill dispatches computed, by kind: real (the "
    "rows' own tokens) and padded (what the bucket's rows x length holds "
    "beyond them: a batched prefill pads every row to the bucket of its "
    "longest and the rows to a power of two). Host arithmetic at "
    "dispatch; padded over real + padded is the share of a prefill's "
    "work spent on nothing",
    labels=("kind",),  # real | padded
    unit="tokens",
    max_series=4,
)
KV_PAGES_FETCHED_TOTAL = REGISTRY.counter(
    "sutro_kv_pages_fetched_total",
    "K/V pages the decode dispatches' attention fetched, a row, a step "
    "and an attention layer at a time (host arithmetic per dispatch: "
    "the paged kernel fetches a row's pages up to its last token's, "
    "the gathered-page path its whole table)",
    unit="pages",
)
KV_PAGES_NEEDED_TOTAL = REGISTRY.counter(
    "sutro_kv_pages_needed_total",
    "Pages the same rows' cached tokens fill (tokens / page size, "
    "fractional), counted as sutro_kv_pages_fetched_total counts: "
    "fetched over needed is the decode attention's over-read",
    unit="pages",
)
KV_READ_TOKENS_TOTAL = REGISTRY.counter(
    "sutro_kv_read_tokens_total",
    "Cached tokens the decode dispatches' attention layers read, a row, "
    "a step and a layer at a time, by the pool (full | window: at most "
    "the window's positions a row) and by whose K/V the layer read: "
    "its OWN pool layer, or ANOTHER layer's (shared: a cross layer that "
    "reads a full layer's K/V again). Host arithmetic per dispatch; "
    "shared over all of it is the share of a step's K/V reads that one "
    "layer's pages serve again",
    labels=("pool", "reader"),  # full | window, own | shared
    unit="tokens",
    max_series=4,
)
STATE_COMMITS_TOTAL = REGISTRY.counter(
    "sutro_state_commits_total",
    "Dispatches that committed per-sequence state (conv columns a "
    "page, or a slot of matrix state) beside K/V, by the path that "
    "committed it",
    labels=("path",),  # prefill | chunk | window | verify | resume
    unit="dispatches",
    max_series=8,
)
LATENT_ATTENTION_DISPATCHES_TOTAL = REGISTRY.counter(
    "sutro_latent_attention_dispatches_total",
    "Dispatches of a model of latent (mla) layers, by the form its "
    "attention took: expanded (a chunk with no past: K and V a head "
    "from the chunk's own latent rows) or absorbed (over the latent "
    "pages: decode steps, fused and speculative windows, verify chunks, "
    "chunked and suffix prefill)",
    labels=("form",),  # expanded | absorbed
    unit="dispatches",
    max_series=4,
)
# Generation by blocks (a model with ``block_length`` > 1:
# engine/runner.py ``_decode_block_jit``, OBSERVABILITY.md "Generation
# by blocks")
BLOCK_FORWARDS_TOTAL = REGISTRY.counter(
    "sutro_block_forwards_total",
    "Forwards of a block the windows of a model that generates by "
    "blocks ran, by kind: a denoising forward reads the block's logits "
    "and keeps no K/V, a commit forward keeps the filled block's K/V",
    labels=("kind",),  # denoise | commit
    unit="forwards",
    max_series=4,
)
BLOCK_ROW_FORWARDS_TOTAL = REGISTRY.counter(
    "sutro_block_row_forwards_total",
    "The same a live row of the window: forwards x rows, by kind",
    labels=("kind",),
    unit="forwards",
    max_series=4,
)
BLOCK_TOKENS_TOTAL = REGISTRY.counter(
    "sutro_block_tokens_total",
    "Positions of the blocks the fetched windows filled, by fate: "
    "accepted into a row's output, given (a prompt's leftover tokens at "
    "the head of a row's first block) or lost (behind a row's stop "
    "token or cap, or of a row that was gone)",
    labels=("fate",),  # accepted | given | lost
    unit="tokens",
    max_series=4,
)
BLOCK_REFUSALS_TOTAL = REGISTRY.counter(
    "sutro_block_refusals_total",
    "What a model that generates by blocks was asked for and does not "
    "do: refused at submit (a constraint, penalties, a seed a row, "
    "speculation) or fallen back from (a shared prefix, the tiers)",
    labels=("what",),
    unit="requests",
    max_series=12,
)
HC_SUBLAYERS_TOTAL = REGISTRY.counter(
    "sutro_hc_sublayers_total",
    "Sublayers a model whose residual stream is several lanes "
    "(hc_mult; manifold-constrained hyper-connections) ran, a forward "
    "step a count of its own: each computes its coefficients and the "
    "Sinkhorn-projected mixing matrix for every token of the step, by "
    "the dispatch's form",
    labels=("form",),  # prefill | decode
    unit="sublayers",
    max_series=4,
)
HC_STREAM_BYTES_NEEDED_TOTAL = REGISTRY.counter(
    "sutro_hc_stream_bytes_needed_total",
    "Bytes the residual stream of the same dispatches' real tokens had "
    "to move: a sublayer reads the lanes once and writes them once "
    "(runner.stream_bytes)",
    unit="bytes",
)
SAMPLE_DISPATCHES_TOTAL = REGISTRY.counter(
    "sutro_sample_dispatches_total",
    "Dispatches that sample (a masked decode step, a fused or speculative "
    "window, an admission group's first tokens), by the side of "
    "ops.sampling.sample's cond the device took: argmax (every row of "
    "the batch at temperature 0: no head, no cumulative sum, no draw) or "
    "drawn (some row draws: the whole stochastic path, for every row)",
    labels=("head",),  # argmax | drawn
    unit="dispatches",
    max_series=4,
)
SPARSE_ATTENTION_DISPATCHES_TOTAL = REGISTRY.counter(
    "sutro_sparse_attention_dispatches_total",
    "Dispatches of a model whose latent layers have an indexer (learned "
    "sparse attention), by whether the selection bites: selected (some "
    "query has more than index_topk positions to choose from: the "
    "indexer's top-k, then attention over the chosen rows) or dense_short "
    "(every row at or under index_topk: the dense latent paths, exact)",
    labels=("path",),  # selected | dense_short
    unit="dispatches",
    max_series=4,
)
SPARSE_ATTENTION_ROWS_TOTAL = REGISTRY.counter(
    "sutro_sparse_attention_rows_total",
    "Cached rows of the decode dispatches of such a model, a row-step "
    "at a time (host arithmetic from the rows' lengths): context (what "
    "the row's past holds, itself included), selected (what its "
    "attention reads: at most index_topk) and fetched (what it moves to "
    "read them: the rows of the row's pages under the paged kernel, the "
    "selected rows themselves where XLA gathers them by position). "
    "selected over context is the share of the cache the attention "
    "reads (1.0 is the dense path); fetched over selected is what the "
    "page walk pays for reading them where they lie",
    labels=("kind",),  # context | selected | fetched
    unit="rows",
    max_series=4,
)
STATE_FALLBACK_PREFILL_TOKENS_TOTAL = REGISTRY.counter(
    "sutro_state_fallback_prefill_tokens_total",
    "Prompt tokens prefilled again because a path could not restore the "
    "per-sequence state at the position it resumed from",
    # hibernated_tail_page | tier_payload_without_state (state a page);
    # prefix_without_state_snapshot | hibernate_without_slot_state
    # (state a slot: no page holds it); prefix_without_window_pages |
    # hibernate_without_window_pages (K/V a pool a kind: a shared or
    # tiered page has no window page); prefix_on_latent_pool |
    # hibernate_on_latent_pool (a latent row a token: kvcache.py);
    # prefix_on_block_model | hibernate_on_block_model (generation by
    # blocks: scheduler.py)
    labels=("reason",),
    unit="tokens",
    max_series=16,
)
KV_PAGES = REGISTRY.gauge(
    "sutro_kv_pages",
    "Pages of the K/V pools of a model that keeps K/V a pool a kind "
    "(garbage pages not counted): the full layers' pool and the window "
    "layers', free and used",
    labels=("kind", "state"),  # full | window; free | used
    unit="pages",
    max_series=8,
)
KV_WINDOW_PAGES_RELEASED_TOTAL = REGISTRY.counter(
    "sutro_kv_window_pages_released_total",
    "Window-pool pages taken back from a live sequence because their "
    "last position had slid out of the window (a row's end gives its "
    "pages back uncounted)",
    unit="pages",
)
KV_WINDOW_PAGES_HELD_TOTAL = REGISTRY.counter(
    "sutro_kv_window_pages_held_total",
    "Window-pool pages the decode batch's rows hold, summed over "
    "scheduler iterations",
    unit="pages",
)
KV_WINDOW_PAGES_WHOLE_TOTAL = REGISTRY.counter(
    "sutro_kv_window_pages_whole_total",
    "Pages the same rows' whole contexts fill (what a window layer "
    "would hold in one pool), summed as "
    "sutro_kv_window_pages_held_total is: held over whole is the share "
    "of its K/V a window layer keeps",
    unit="pages",
)
KDA_DISPATCHES_TOTAL = REGISTRY.counter(
    "sutro_kda_dispatches_total",
    "Dispatches of a model with delta-rule (kda) layers, by the form "
    "those layers took: chunked (a prefill: from the slot's state to "
    "its end) or pending (a decode step, a window, a verify chunk: the "
    "committed state read in place and not advanced)",
    labels=("form",),  # chunked | pending
    unit="dispatches",
    max_series=4,
)
KDA_STATE_BYTES_TOTAL = REGISTRY.counter(
    "sutro_kda_state_bytes_total",
    "Bytes of delta-rule state the dispatches' programs moved between "
    "HBM and the chip, by what moved them: read (a step's two products "
    "against a row's slot: the slot once under the kernel, gathered "
    "first on the XLA path) and commit (a row's slot in and out once "
    "under the kernel; gathered, advanced and scattered on the XLA "
    "path; a prefill's final state written)",
    labels=("op",),  # read | commit
    unit="bytes",
    max_series=4,
)
KDA_STATE_BYTES_NEEDED_TOTAL = REGISTRY.counter(
    "sutro_kda_state_bytes_needed_total",
    "Bytes of delta-rule state the same dispatches had to move: a "
    "row's slot read once a step and written once a commit",
    unit="bytes",
)
STATE_SLOTS = REGISTRY.gauge(
    "sutro_state_slots",
    "Slots of the state pool (one a live sequence; the garbage "
    "slot not counted): in use, and in all",
    labels=("state",),  # in_use | total
    unit="slots",
    max_series=4,
)
STATE_SLOT_WAITS_TOTAL = REGISTRY.counter(
    "sutro_state_slot_waits_total",
    "Admissions that waited for a free state slot with a batch row "
    "and pages free",
    unit="admissions",
)
CONSTRAINT_FACTORY_TOTAL = REGISTRY.counter(
    "sutro_constraint_factory_total",
    "Asks of the engine's constraint-factory table (one factory per "
    "(schema, tokenizer), kept across jobs): hit = kept, miss = this "
    "ask built it, wait = served by another thread's build in flight",
    labels=("result",),  # hit | miss | wait
    unit="lookups",
    max_series=4,
)
KV_RESUMES_TOTAL = REGISTRY.counter(
    "sutro_kv_resumes_total",
    "Preempted-row resumes by mechanism: 'upload' re-admits from a "
    "hibernated host/disk payload (page-upload, no prefill); "
    "'reprefill' regenerates from scratch (tier miss / torn promotion)",
    labels=("kind",),  # upload | reprefill
    unit="rows",
    max_series=4,
)
FLEET_REPLICAS = REGISTRY.gauge(
    "sutro_fleet_replicas",
    "Fleet router replica census by state (healthy = breaker closed + "
    "ready + not draining; open/half_open = breaker tripped; draining "
    "= alive, refusing new work)",
    labels=("state",),  # healthy | open | half_open | draining
    max_series=8,
)
FLEET_FAILOVERS_TOTAL = REGISTRY.counter(
    "sutro_fleet_failovers_total",
    "Requests/jobs moved off a failed replica: 'batch' = jobstore "
    "resume_job re-submission after a replica death mid-job, "
    "'interactive' = transparent pre-first-token retry on another "
    "replica, 'stream_error' = post-first-token structured mid-stream "
    "error returned to the client (no transparent retry possible)",
    labels=("kind",),  # batch | interactive | stream_error
    max_series=8,
)
FLEET_ROUTED_PREFIX_HITS_TOTAL = REGISTRY.counter(
    "sutro_fleet_routed_prefix_hits_total",
    "Interactive requests routed to a replica reporting > 0 warm "
    "prefix tokens (the SGLang-style cache-aware routing win)",
)
FLEET_ROUTE_SECONDS = REGISTRY.histogram(
    "sutro_fleet_route_seconds",
    "Router time from request arrival to the routing decision landing "
    "on a replica (candidate scoring + affinity probe + upstream "
    "connect, retries included); exemplars carry the router trace id",
    labels=("kind",),  # interactive | batch
    unit="seconds",
    max_series=8,
)

# -- the scheduler's own accounting (engine/scheduler.py run_multi) ------
SCHED_ITERATIONS_TOTAL = REGISTRY.counter(
    "sutro_sched_iterations_total",
    "Scheduler loop iterations by the decode path each took: pipelined "
    "| window | fastforward | single, or idle when no row was ready to "
    "decode",
    labels=("path",),
    max_series=8,
)
SCHED_DISPATCH_ROWS_TOTAL = REGISTRY.counter(
    "sutro_sched_dispatch_rows_total",
    "Active decode rows summed over the iterations whose path is not "
    "idle (over iterations x decode_batch_size: batch occupancy)",
    unit="rows",
)
# how often admission waits for the device (OBSERVABILITY.md "The
# admission wave"): rows over waves is the rows a host sync; 1.0 is a
# sync a row
ADMIT_WAVES_TOTAL = REGISTRY.counter(
    "sutro_admit_waves_total",
    "Admission waves resolved: host syncs that fetched the first "
    "tokens of the prefills dispatched since the one before",
    unit="waves",
)
ADMIT_WAVE_ROWS_TOTAL = REGISTRY.counter(
    "sutro_admit_wave_rows_total",
    "Rows those waves armed (over sutro_admit_waves_total: rows a "
    "host sync; a job that streams its tokens cuts the wave at its row)",
    unit="rows",
)
ADMIT_WAVE_JOINED_ROWS_TOTAL = REGISTRY.counter(
    "sutro_admit_wave_joined_rows_total",
    "The same rows by how each entered its first decode dispatch: "
    "device (the window was dispatched before the row's first token "
    "reached the host, which took it from the device) | host (the wave "
    "was resolved first: a constrained, seeded, penalised or streaming "
    "row, or no pipelined window to enter)",
    labels=("joined",),
    unit="rows",
    max_series=4,
)
DECODE_AHEAD_WINDOWS_TOTAL = REGISTRY.counter(
    "sutro_decode_ahead_windows_total",
    "Fused windows asked for AHEAD of one in flight, by what the "
    "scheduler did (_pipe_capacity_ok): sent | held_unused (the windows "
    "in flight end every row) | held_ending (rows wait for slots and "
    "over _AHEAD_ENDING of the batch ends in flight)",
    labels=("ahead",),
    unit="windows",
    max_series=4,
)
# what the decode dispatches yield (OBSERVABILITY.md "What a decode
# dispatch yields"): a row-step is one position of one live row in one
# dispatch at which a token could have been committed; per path,
# row-steps = tokens committed + row-steps lost, summed over reasons
SCHED_ROW_STEPS_TOTAL = REGISTRY.counter(
    "sutro_sched_row_steps_total",
    "Row-steps of the decode dispatches accepted, by path: the window's "
    "steps a row (pipelined, window), the verify forward's width "
    "constrain_fastforward + 1 a row (fastforward), one a row (single)",
    labels=("path",),  # pipelined | window | fastforward | single
    unit="row_steps",
    max_series=8,
)
SCHED_TOKENS_COMMITTED_TOTAL = REGISTRY.counter(
    "sutro_sched_tokens_committed_total",
    "Tokens the accept loops committed to live rows, by path (the "
    "prefill-sampled first token of a row is not a decode dispatch's)",
    labels=("path",),
    unit="tokens",
    max_series=8,
)
SCHED_ROW_STEPS_LOST_TOTAL = REGISTRY.counter(
    "sutro_sched_row_steps_lost_total",
    "Row-steps that committed nothing, by path and reason",
    # stale | finished | failed (any path); rejected (window, and a
    # fastforward rider); plan_short | diverged | no_plan (fastforward)
    labels=("path", "reason"),
    unit="row_steps",
    max_series=32,
)
# how a constrained row's mask reached the masked step (OBSERVABILITY.md
# "The FSM masks travel bit-packed"): counted a row in
# ContinuousBatcher._fsm_masks
FSM_MASK_ROWS_TOTAL = REGISTRY.counter(
    "sutro_fsm_mask_rows_total",
    "Constrained rows whose FSM mask the mask assembly wrote, by how it "
    "came by the bit-packed row: cached (a kept packed array copied, no "
    "pass over the vocabulary), filtered (the token budget bit: one row "
    "computed and packed), packed_here (a constraint that answers in "
    "bools only)",
    labels=("path",),  # cached | filtered | packed_here
    unit="rows",
    max_series=4,
)

# Span names the engine emits — OBSERVABILITY.md's span schema section
# and tests key off this tuple, so additions land in one place.
STAGES = (
    "tokenize",
    "constraint_compile",
    "admit",
    "prefill",
    "decode_window",
    "accept",
    "flush",
    "finalize",
    "dp_round",
    "embed",
    # tiered-KV migration hops (engine/kvtier.py): device<->host page
    # payload moves on the scheduler thread (disk writes happen on the
    # migration worker and surface as kv_demote queue time only)
    "kv_demote",
    "kv_promote",
    # the scheduler thread's own phases (engine/profiling.py StepTimer's
    # cursor): with the device-dispatch stages above they tile every
    # instant of run_multi, so sums of their seconds double count nothing
    "sched_poll",
    "job_start",
    "admit_host",
    "fsm_mask",
    "fsm_plan",
    "batch_build",
    "emit",
    "sched_idle",
    "sched_other",
    # lazy constraint builds on the admission-prep thread: they overlap
    # the phases above (constraint_compile is scheduler-thread builds)
    "constraint_prep",
)


def stage_observe(
    stage: str, dur_s: float, exemplar: Optional[str] = None
) -> None:
    """One engine stage latency sample into the registry histogram
    (the flight-recorder span is the caller's concern — spans carry
    job identity, the histogram does not). ``exemplar`` optionally
    pins a trace id to the sample's bucket (forensics). Internally
    gated: callers on hot paths may invoke it bare and still honor
    the kill switch."""
    if not ENABLED:
        return
    STAGE_SECONDS.observe(dur_s, stage, exemplar=exemplar)


def job(job_id: str) -> JobCounters:
    return JOBS.job(job_id)


# -- per-job document / flight-recorder dump ---------------------------

# v2: adds per-job "attrs" (device info, profile trace path) and, for
# dp coordinator jobs, "workers" — the ingested per-rank sections
# (telemetry/distributed.py), merged by (round, rank)
SCHEMA_VERSION = 2


def job_doc(job_id: str) -> Dict[str, Any]:
    """Assemble the per-job telemetry document from live state: the
    job's span timeline (flight recorder) + its exact counters, plus —
    on a dp coordinator — every ingested worker section (the merged
    cross-process timeline the doctor analyzes)."""
    jc = JOBS.peek(job_id)
    spans = RECORDER.snapshot(job_id)
    doc: Dict[str, Any] = {
        "version": SCHEMA_VERSION,
        "job_id": job_id,
        "dumped_at": time.strftime(
            "%Y-%m-%dT%H:%M:%S%z", time.localtime()
        ),
        "recorder": {
            "capacity": RECORDER.capacity,
            "dropped": RECORDER.dropped,
            "epoch_unix": RECORDER.epoch_wall,
        },
        "counters": jc.to_dict() if jc is not None else {},
        "stages": sorted({s["name"] for s in spans}),
        "spans": spans,
    }
    if jc is not None and jc.attrs:
        doc["attrs"] = dict(jc.attrs)
    workers = distributed.REMOTE.sections(job_id)
    if workers:
        doc["workers"] = workers
        doc["stages"] = sorted(
            set(doc["stages"])
            | {
                s["name"]
                for w in workers
                for s in w.get("spans", ())
            }
        )
    return doc


def dump_job(job_dir: Path, job_id: str) -> Optional[Dict[str, Any]]:
    """Write ``telemetry.json`` into the job directory (atomic rename,
    jobstore convention). Best-effort: recording a postmortem must
    never become a new failure. Returns the doc (or None on failure/
    disabled)."""
    if not ENABLED:
        return None
    try:
        doc = job_doc(job_id)
        SPANS_DROPPED.set(RECORDER.dropped)
        path = Path(job_dir) / "telemetry.json"
        tmp = path.with_suffix(".json.tmp")
        tmp.write_text(json.dumps(doc, indent=2))
        tmp.replace(path)
        return doc
    except Exception:
        logger.warning(
            "telemetry dump failed for %s", job_id, exc_info=True
        )
        return None


def load_job_dump(job_dir: Path) -> Optional[Dict[str, Any]]:
    path = Path(job_dir) / "telemetry.json"
    if not path.exists():
        return None
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        logger.warning("unreadable telemetry.json in %s: %s", job_dir, e)
        return None


def reset_for_tests() -> None:
    """Drop accumulated registry/recorder/job state (declarations
    stay). Tests only."""
    REGISTRY.reset()
    RECORDER.clear()
    TRACES.clear()
    for jc in JOBS:
        JOBS.drop(jc.job_id)
    distributed.REMOTE.clear()


# imported last: distributed.py / monitor.py resolve the package
# singletons above lazily at call time, so the bottom imports only
# publish the names
from . import distributed  # noqa: E402
from . import monitor  # noqa: E402
from . import traceexport  # noqa: E402
