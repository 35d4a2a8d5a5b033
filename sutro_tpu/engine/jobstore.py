"""Job store: records, states, durable results, quotas, cost model.

Replaces the remote service's job control plane (reference wire contract
SURVEY §3.6: /jobs/{id}, /job-status/{id}, /list-jobs, /job-results,
/job-cancel, /get-quotas). Layout under ``$SUTRO_HOME/jobs/<job_id>/``:

- ``record.json``   — the job record (status, counters, timestamps, config)
- ``inputs.parquet``  — materialized input rows (row_id, inputs)
- ``partial/``      — completed rows flushed during the run as immutable
  chunk files ``b<bucket>-s<seq>.parquet`` (bucket = row_id //
  chunk_rows, seq = per-flush monotonic counter). Each flush writes
  ONLY its own rows — O(chunk) per flush instead of the old
  read-concat-rewrite of ``partial.parquet`` (O(total), quadratic over
  a job). A legacy ``partial.parquet`` is still read (seq −1) so
  pre-upgrade jobs resume.
- ``results.parquet`` — final ordered results. Generation jobs write it
  with ``write_results_streamed``: a merge-on-read pass over the
  partial buckets, one row-group per bucket, so peak host memory is
  O(chunk_rows), not O(job).

Invariants (SURVEY §5.2 — replace the reference's results-availability
retry race, sdk.py:384-401, with real guarantees):

- single writer: only the engine worker thread mutates a running job;
- ``results.parquet`` is fully written and flushed *before* the record
  flips to SUCCEEDED, so "status==SUCCEEDED" implies "results readable".
"""

from __future__ import annotations

import dataclasses
import datetime
import json
import logging
import threading
import time
import uuid
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import pandas as pd

logger = logging.getLogger(__name__)

from .. import telemetry
from ..interfaces import JobStatus
from ..validation import config_dir
from . import faults
from .faults import retry_transient
from .profiling import host_leaf

# ---------------------------------------------------------------------------
# Cost model (USD per 1M tokens). The reference surfaces only a server-side
# `cost_estimate` (sdk.py:245-262); this local model prices by param
# count. The prices are stated, not derived from a measured rate: no
# chip run stands behind them (PERF_LEDGER.jsonl holds what was measured).
# ---------------------------------------------------------------------------

COST_PER_MTOK: Dict[str, Dict[str, float]] = {
    # engine_key prefix -> {input, output}
    "qwen3-0.6b": {"input": 0.01, "output": 0.02},
    "qwen3-4b": {"input": 0.04, "output": 0.08},
    "qwen3-8b": {"input": 0.07, "output": 0.15},
    "qwen3-14b": {"input": 0.12, "output": 0.25},
    "qwen3-32b": {"input": 0.25, "output": 0.50},
    "qwen3-30b-a3b": {"input": 0.10, "output": 0.20},
    "qwen3-235b-a22b": {"input": 0.50, "output": 1.00},
    "llama-3.2-3b": {"input": 0.03, "output": 0.06},
    "llama-3.1-8b": {"input": 0.07, "output": 0.15},
    "llama-3.3-70b": {"input": 0.45, "output": 0.90},
    "gemma3-4b": {"input": 0.04, "output": 0.08},
    "gemma3-12b": {"input": 0.10, "output": 0.22},
    "gemma3-27b": {"input": 0.22, "output": 0.45},
    "gpt-oss-20b": {"input": 0.06, "output": 0.12},
    "gpt-oss-120b": {"input": 0.25, "output": 0.50},
    "qwen3-emb-0.6b": {"input": 0.01, "output": 0.01},
    "qwen3-emb-6b": {"input": 0.05, "output": 0.05},
    "qwen3-emb-8b": {"input": 0.07, "output": 0.07},
}
_DEFAULT_COST = {"input": 0.10, "output": 0.20}

# Per-priority quotas (rows, tokens) — reference /get-quotas shape: a list
# indexed by priority, each {row_quota, token_quota} (sdk.py:1547-1561,
# cli.py:406-411). NOTE on the BASELINE "priority -> pod-slice size"
# mapping: in this build priority selects quota table + scheduling
# precedence (p0 preempts running p1 jobs, tests/test_priority.py), NOT
# engine/pod sizing. Slice-count selection per priority belongs to the
# pod launcher, which sets SUTRO_DP_WORLD per engine process group
# (engine/dphost.py); a single-host engine has nothing to size. Recorded
# as out of scope in PARITY.md.
DEFAULT_QUOTAS: List[Dict[str, int]] = [
    {"row_quota": 500_000, "token_quota": 500_000_000},
    {"row_quota": 5_000_000, "token_quota": 5_000_000_000},
]


class InvalidPriority(ValueError):
    """Out-of-range ``job_priority`` at submit. Structured (PAPER.md
    quota semantics): priorities index the quota table, so a value
    outside it is a caller error, not something to silently clamp.
    The HTTP layer maps this to 400 with ``code=INVALID_PRIORITY``."""

    code = "INVALID_PRIORITY"
    status = 400

    def __init__(self, priority: Any, n_levels: int) -> None:
        self.priority = priority
        self.n_levels = n_levels
        super().__init__(
            f"job_priority {priority!r} is out of range: the quota "
            f"table defines priorities 0..{n_levels - 1}"
        )


def estimate_cost(
    engine_key: str, input_tokens: int, output_tokens: int
) -> float:
    rates = COST_PER_MTOK.get(engine_key, _DEFAULT_COST)
    return (
        input_tokens * rates["input"] + output_tokens * rates["output"]
    ) / 1e6


def _now() -> str:
    return datetime.datetime.now(datetime.timezone.utc).isoformat()


@dataclasses.dataclass
class JobRecord:
    job_id: str
    status: str = JobStatus.QUEUED.value
    name: Optional[str] = None
    description: Optional[str] = None
    model: str = ""
    engine_key: str = ""
    num_rows: int = 0
    job_priority: int = 0
    datetime_created: str = dataclasses.field(default_factory=_now)
    datetime_started: Optional[str] = None
    datetime_completed: Optional[str] = None
    input_tokens: int = 0
    output_tokens: int = 0
    cost_estimate: Optional[float] = None
    job_cost: Optional[float] = None
    failure_reason: Optional[Dict[str, Any]] = None
    # structured, bounded event trail: every retry / per-row quarantine /
    # terminal failure appends here (reference sessions carry the same
    # ``failure_log[]`` — SURVEY §5.3; schema in FAILURES.md)
    failure_log: Optional[List[Dict[str, Any]]] = None
    output_schema: Optional[Dict[str, Any]] = None
    system_prompt: Optional[str] = None
    sampling_params: Optional[Dict[str, Any]] = None
    truncate_rows: bool = True
    dry_run: bool = False
    random_seed_per_input: bool = False
    # tenant attribution (telemetry/monitor.py): submit-time identity
    # every series and terminal accounting row is keyed by; "default"
    # when the caller names none
    tenant: Optional[str] = None
    # per-job latency profile (engine/profiling.py StepTimer.summary())
    perf: Optional[Dict[str, Any]] = None
    # Stage-graph job (engine/stagegraph.py): the validated stage list
    # exactly as submitted (None for plain jobs — the off switch), plus
    # a durable per-stage rollup {name: {status, rows_done, rows_total,
    # quarantined}} updated as stage chunks finalize. Both ride the
    # record's forward-compatible JSON (get() filters unknown keys), so
    # old records and stage-less jobs round-trip untouched.
    stages: Optional[List[Dict[str, Any]]] = None
    stages_state: Optional[Dict[str, Any]] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class JobStore:
    # failure_log entries kept per job (oldest dropped first): the log is
    # an incident trail, not a metrics store — bounded so a pathological
    # job can't grow its record without limit
    _FAILURE_LOG_CAP = 200

    def __init__(
        self,
        root: Optional[Path] = None,
        chunk_rows: Optional[int] = None,
        io_retries: Optional[int] = None,
        io_backoff: Optional[float] = None,
        io_backoff_cap: Optional[float] = None,
    ):
        import os

        self.root = Path(root) if root else (config_dir() / "jobs")
        self.root.mkdir(parents=True, exist_ok=True)
        # result/partial chunk granularity: the unit of per-flush I/O
        # AND the peak materialized row count during finalization
        self.chunk_rows = int(
            chunk_rows
            if chunk_rows is not None
            else os.environ.get("SUTRO_RESULT_CHUNK", "1024")
        )
        if self.chunk_rows < 1:
            self.chunk_rows = 1
        # transient-I/O retry policy (exponential backoff + jitter,
        # bounded attempts — engine/faults.retry_transient): a blip in
        # the store must not fail a 20k-row job, a dead disk still must
        self.io_retries = int(
            io_retries
            if io_retries is not None
            else os.environ.get("SUTRO_IO_RETRIES", "4")
        )
        self.io_backoff = float(
            io_backoff
            if io_backoff is not None
            else os.environ.get("SUTRO_IO_BACKOFF", "0.05")
        )
        self.io_backoff_cap = float(
            io_backoff_cap
            if io_backoff_cap is not None
            else os.environ.get("SUTRO_IO_BACKOFF_CAP", "2.0")
        )
        self._lock = threading.Lock()
        self._flush_seq: Dict[str, int] = {}  # job_id -> next chunk seq
        # terminal-transition hook (engine/control.py refunds a job's
        # unused admission reserve here). Called once per terminal
        # transition with the fresh JobRecord; best-effort — a hook
        # error must never corrupt the status funnel.
        self.on_terminal: Optional[Callable[[JobRecord], None]] = None

    # -- paths -----------------------------------------------------------
    def _dir(self, job_id: str) -> Path:
        return self.root / job_id

    def _record_path(self, job_id: str) -> Path:
        return self._dir(job_id) / "record.json"

    # -- record lifecycle ------------------------------------------------
    def create(self, **fields: Any) -> JobRecord:
        job_id = fields.pop("job_id", None) or f"job-{uuid.uuid4().hex[:16]}"
        rec = JobRecord(job_id=job_id, **fields)
        d = self._dir(job_id)
        d.mkdir(parents=True, exist_ok=True)
        self._write_record(rec)
        return rec

    def _write_record(self, rec: JobRecord) -> None:
        path = self._record_path(rec.job_id)
        tmp = path.with_suffix(".json.tmp")
        # small local record write; when reached from ``update`` it runs
        # under the store lock — that read-modify-write IS the lock's
        # critical section
        # graftlint: disable=lock-blocking-call
        tmp.write_text(json.dumps(rec.to_dict(), indent=2))
        tmp.replace(path)  # atomic on POSIX

    def get(self, job_id: str) -> JobRecord:
        path = self._record_path(job_id)
        if not path.exists():
            raise KeyError(f"Unknown job: {job_id}")
        # tiny local JSON; under the lock only via ``update`` (the RMW)
        # graftlint: disable=lock-blocking-call
        data = json.loads(path.read_text())
        fields = {f.name for f in dataclasses.fields(JobRecord)}
        return JobRecord(**{k: v for k, v in data.items() if k in fields})

    def update(self, job_id: str, **fields: Any) -> JobRecord:
        with self._lock:
            rec = self.get(job_id)
            for k, v in fields.items():
                setattr(rec, k, v)
            self._write_record(rec)
            return rec

    def set_status(self, job_id: str, status: JobStatus, **extra: Any) -> None:
        fields: Dict[str, Any] = {"status": status.value, **extra}
        if status == JobStatus.RUNNING:
            fields.setdefault("datetime_started", _now())
        if status.is_terminal():
            fields.setdefault("datetime_completed", _now())
        rec = self.update(job_id, **fields)
        if telemetry.ENABLED and status in (
            JobStatus.SUCCEEDED, JobStatus.FAILED, JobStatus.CANCELLED
        ):
            # terminal TRANSITIONS (a resumed-then-failed job counts
            # twice — each is a real lifecycle event)
            telemetry.JOBS_TOTAL.inc(1.0, status.value.lower())
            # tenant attribution settles at the same funnel: rows from
            # the job's exact counters, tokens from the record's
            # accounting — every terminal path (generate, embed, dp,
            # resume) passes through here exactly once per transition
            tenant = str(rec.tenant or "default")
            jc = telemetry.JOBS.peek(job_id)
            if jc is not None:
                d = jc.to_dict()
                if d.get("rows_ok"):
                    telemetry.TENANT_ROWS_TOTAL.inc(
                        float(d["rows_ok"]), tenant, "ok"
                    )
                if d.get("rows_quarantined"):
                    telemetry.TENANT_ROWS_TOTAL.inc(
                        float(d["rows_quarantined"]), tenant,
                        "quarantined",
                    )
            if rec.input_tokens:
                telemetry.TENANT_TOKENS_TOTAL.inc(
                    float(rec.input_tokens), tenant, "in"
                )
            if rec.output_tokens:
                telemetry.TENANT_TOKENS_TOTAL.inc(
                    float(rec.output_tokens), tenant, "out"
                )
        if telemetry.ENABLED and status == JobStatus.CANCELLED:
            # CANCELLED dumps the flight recorder like FAILED does
            # (engine/api.py handles FAILED at its failure boundaries):
            # a cancelled 20k-row job is exactly when an operator asks
            # "how far did it get, and why was it slow" — this is the
            # one funnel every cancel path passes through
            telemetry.dump_job(self._dir(job_id), job_id)
        if status.is_terminal() and self.on_terminal is not None:
            try:
                self.on_terminal(rec)
            except Exception:  # noqa: BLE001 — the hook (control-plane
                # refund) is best-effort; the status funnel is not
                logger.warning(
                    "on_terminal hook failed for %s", job_id,
                    exc_info=True,
                )

    def status(self, job_id: str) -> JobStatus:
        return JobStatus(self.get(job_id).status)

    def append_failure_log(
        self, job_id: str, event: Dict[str, Any]
    ) -> None:
        """Append one structured event to the job's bounded
        ``failure_log[]`` (retry / quarantine / terminal failure — the
        reference session schema). Best-effort by design: recording a
        recovery must never itself become a new failure. ``ts`` is
        stamped here so callers only describe the event."""
        ev = {"ts": _now(), **event}
        if telemetry.ENABLED:
            # the single funnel every retry/quarantine/terminal event
            # passes through — one counter covers them all. The label
            # domain is the fixed event-kind vocabulary; a non-string
            # (malformed caller) collapses to one series instead of
            # str()-coercing arbitrary objects into label values.
            kind = event.get("event")
            telemetry.ROW_EVENTS_TOTAL.inc(
                1.0, kind if isinstance(kind, str) else "unknown"
            )
        try:
            # inline RMW (``update`` would re-take the non-reentrant
            # store lock); the record write IS the critical section
            with self._lock:
                rec = self.get(job_id)
                log = list(rec.failure_log or [])
                log.append(ev)
                if len(log) > self._FAILURE_LOG_CAP:
                    log = log[-self._FAILURE_LOG_CAP :]
                rec.failure_log = log
                self._write_record(rec)
        except Exception:
            logger.warning(
                "failure_log append failed for %s (event %r)",
                job_id, event.get("event"), exc_info=True,
            )

    def list_jobs(self) -> List[Dict[str, Any]]:
        """Newest-first job records (reference /list-jobs, cli.py:157-196)."""
        out = []
        for d in self.root.iterdir():
            if (d / "record.json").exists():
                try:
                    out.append(self.get(d.name).to_dict())
                except (KeyError, TypeError, ValueError, OSError) as e:
                    # a torn/foreign record must not break the listing,
                    # but the skip has to be visible
                    logger.warning(
                        "skipping unreadable job record %s: %s", d.name, e
                    )
                    continue
        out.sort(key=lambda r: r.get("datetime_created") or "", reverse=True)
        return out

    def delete(self, job_id: str) -> None:
        import shutil

        shutil.rmtree(self._dir(job_id), ignore_errors=True)

    # -- inputs / results -------------------------------------------------
    def write_inputs(self, job_id: str, rows: List[str]) -> None:
        df = pd.DataFrame({"row_id": range(len(rows)), "inputs": rows})
        df.to_parquet(self._dir(job_id) / "inputs.parquet")

    def read_inputs(self, job_id: str) -> List[str]:
        df = pd.read_parquet(self._dir(job_id) / "inputs.parquet")
        return df.sort_values("row_id")["inputs"].tolist()

    def _partial_dir(self, job_id: str) -> Path:
        return self._dir(job_id) / "partial"

    def _partial_chunks(self, job_id: str) -> List[tuple]:
        """All partial chunk files as ``(bucket, seq, path)``, unsorted.
        Filenames are ``b<bucket>-s<seq>.parquet``; later seq wins on
        duplicate row_ids (a resumed run regenerating a cancelled row
        flushes a fresh entry with a higher seq)."""
        d = self._partial_dir(job_id)
        if not d.is_dir():
            return []
        out = []
        for p in d.iterdir():
            name = p.name
            if not (name.startswith("b") and name.endswith(".parquet")):
                continue
            try:
                b_part, s_part = name[1 : -len(".parquet")].split("-s")
                out.append((int(b_part), int(s_part), p))
            except ValueError:
                continue
        return out

    def _next_flush_seq(self, job_id: str) -> int:
        with self._lock:
            seq = self._flush_seq.get(job_id)
            if seq is None:  # first flush this process: resume the count
                seq = (
                    max(
                        (s for _, s, _ in self._partial_chunks(job_id)),
                        default=-1,
                    )
                    + 1
                )
            self._flush_seq[job_id] = seq + 1
            return seq

    def flush_partial(self, job_id: str, rows: List[Dict[str, Any]]) -> None:
        """Append-flush completed rows for row-granular resume (§5.3).

        O(len(rows)) per call: each flush lands as immutable chunk
        files under ``partial/`` split by row_id bucket (the old
        single-file scheme re-read and re-wrote the WHOLE partial store
        every flush — quadratic over a long job).

        Transient-fault domain: OSError flushes retry with exponential
        backoff + jitter, bounded by ``io_retries``, each retry recorded
        in the job's ``failure_log[]``; chunks are idempotent (a fresh
        seq per attempt, later seq wins on duplicate row_ids), so a
        half-landed attempt is harmless."""
        if not rows:
            return
        # under the scheduler's ``emit`` phase (on_result runs on its
        # thread) the flush is a leaf of its own, recorded here
        with host_leaf("flush"):
            t0 = time.monotonic()
            retry_transient(
                lambda: self._flush_partial_once(job_id, rows),
                attempts=self.io_retries,
                base=self.io_backoff,
                cap=self.io_backoff_cap,
                retry_on=(OSError,),
                on_retry=lambda attempt, delay, exc: (
                    self.append_failure_log(
                        job_id,
                        {"event": "io_retry",
                         "site": "jobstore.flush_partial",
                         "attempt": attempt,
                         "error": f"{type(exc).__name__}: {exc}"},
                    )
                ),
                what=f"flush_partial[{job_id}]",
            )
            if telemetry.ENABLED:
                dt = time.monotonic() - t0
                telemetry.stage_observe("flush", dt)
                telemetry.RECORDER.record(
                    "flush", job_id, t0, dt, {"rows": len(rows)}
                )

    def _flush_partial_once(
        self, job_id: str, rows: List[Dict[str, Any]]
    ) -> None:
        if faults.ACTIVE is not None:
            spec = faults.fire("jobstore.flush_partial", job=job_id)
            if spec is not None:
                if spec.kind == "torn":
                    # simulate a crash mid-flush on a non-durable fs:
                    # a chunk file exists at its FINAL name with only
                    # part of its bytes (readers must skip+quarantine
                    # it; the retry lands a good chunk at a higher seq)
                    self._write_torn_chunk(job_id, rows)
                spec.trigger()
        d = self._partial_dir(job_id)
        d.mkdir(parents=True, exist_ok=True)
        seq = self._next_flush_seq(job_id)
        by_bucket: Dict[int, List[Dict[str, Any]]] = {}
        for r in rows:
            by_bucket.setdefault(
                int(r["row_id"]) // self.chunk_rows, []
            ).append(r)
        for bucket, rs in by_bucket.items():
            df = pd.DataFrame(rs).sort_values("row_id")
            path = d / f"b{bucket:08d}-s{seq:08d}.parquet"
            tmp = path.with_suffix(".parquet.tmp")
            df.to_parquet(tmp)
            tmp.replace(path)  # atomic on POSIX

    def _write_torn_chunk(
        self, job_id: str, rows: List[Dict[str, Any]]
    ) -> None:
        """Fault-plan helper (kind ``torn``): land a truncated chunk
        file at a real chunk name, as a crash between write and fsync
        would on a non-durable filesystem."""
        import io

        d = self._partial_dir(job_id)
        d.mkdir(parents=True, exist_ok=True)
        seq = self._next_flush_seq(job_id)
        bucket = int(rows[0]["row_id"]) // self.chunk_rows
        buf = io.BytesIO()
        pd.DataFrame(rows).to_parquet(buf)
        data = buf.getvalue()
        (d / f"b{bucket:08d}-s{seq:08d}.parquet").write_bytes(
            data[: max(8, len(data) // 2)]
        )

    def _read_chunk(
        self, job_id: str, path: Path, columns: Optional[List[str]] = None
    ) -> Optional[pd.DataFrame]:
        """Read one partial chunk, tolerating a torn/corrupt file (crash
        mid-flush): the bad chunk is quarantined to ``partial/.corrupt/``
        and logged instead of failing the WHOLE store — its rows simply
        regenerate on resume. Returns None for a quarantined chunk."""
        try:
            return pd.read_parquet(path, columns=columns)
        except Exception as e:  # pyarrow raises ArrowInvalid/OSError/...
            logger.warning(
                "quarantining corrupt partial chunk %s: %s", path, e
            )
            try:
                cdir = path.parent / ".corrupt"
                cdir.mkdir(exist_ok=True)
                path.replace(cdir / path.name)
            except OSError:
                logger.warning(
                    "could not quarantine %s", path, exc_info=True
                )
            self.append_failure_log(
                job_id,
                {"event": "torn_chunk_quarantined", "chunk": path.name,
                 "error": f"{type(e).__name__}: {e}"},
            )
            return None

    def _legacy_partial(self, job_id: str) -> Optional[pd.DataFrame]:
        path = self._dir(job_id) / "partial.parquet"
        if not path.exists():
            return None
        return pd.read_parquet(path)

    def read_partial(self, job_id: str) -> Dict[int, Dict[str, Any]]:
        """Full partial rows (legacy file first, then chunks in seq
        order, later writes winning). O(done rows) memory — callers
        that only need row ids/reasons use ``read_partial_meta``."""
        frames: List[pd.DataFrame] = []
        legacy = self._legacy_partial(job_id)
        if legacy is not None:
            frames.append(legacy)
        for _, _, p in sorted(
            self._partial_chunks(job_id), key=lambda t: t[1]
        ):
            df = self._read_chunk(job_id, p)
            if df is not None:
                frames.append(df)
        out: Dict[int, Dict[str, Any]] = {}
        for df in frames:
            for _, r in df.iterrows():
                out[int(r["row_id"])] = dict(r)
        return out

    def read_partial_meta(self, job_id: str) -> Dict[int, str]:
        """row_id -> finish_reason for every flushed row (column
        projection only — the resume filter and done-set bootstrap
        never materialize outputs)."""
        cols = ["row_id", "finish_reason"]
        frames: List[pd.DataFrame] = []
        legacy = self._legacy_partial(job_id)
        if legacy is not None:
            frames.append(legacy[cols])
        for _, _, p in sorted(
            self._partial_chunks(job_id), key=lambda t: t[1]
        ):
            df = self._read_chunk(job_id, p, columns=cols)
            if df is not None:
                frames.append(df)
        out: Dict[int, str] = {}
        for df in frames:
            ids = df["row_id"].to_numpy()
            reasons = df["finish_reason"].tolist()
            for i, reason in zip(ids, reasons):
                out[int(i)] = reason
        return out

    def finalize_results(
        self, job_id: str, results: Dict[str, List[Any]]
    ) -> None:
        """Write final results THEN flip to SUCCEEDED (ordering invariant).
        Materializes the whole frame — kept for the embedding path
        (vector-valued outputs); generation jobs use
        ``write_results_streamed``."""
        t0 = time.monotonic()
        df = pd.DataFrame(results)
        tmp = self._dir(job_id) / "results.parquet.tmp"
        df.to_parquet(tmp)
        tmp.replace(self._dir(job_id) / "results.parquet")
        if telemetry.ENABLED:
            dt = time.monotonic() - t0
            telemetry.stage_observe("finalize", dt)
            telemetry.RECORDER.record(
                "finalize", job_id, t0, dt, {"rows": len(df)}
            )
        self.set_status(job_id, JobStatus.SUCCEEDED)

    # generation result schema: one definition so every row-group of a
    # streamed results.parquet agrees with what finalize_results used
    # to produce via pandas. ``error`` carries a quarantined row's
    # failure message (null for clean rows) — SUCCEEDED with N-k good
    # rows + k error rows, instead of one bad row failing the job.
    _GEN_COLS = (
        "row_id",
        "outputs",
        "cumulative_logprobs",
        "gen_tokens",
        "finish_reason",
        "error",
    )

    # columns absent from pre-upgrade partial rows that backfill with a
    # default instead of raising (anything else missing is a bug)
    _GEN_BACKFILL = ("gen_tokens", "error")

    def write_results_streamed(
        self,
        job_id: str,
        num_rows: int,
        on_chunk=None,
    ) -> None:
        """Merge-on-read finalization: assemble ``results.parquet`` in
        row_id order directly from the partial chunk store, one bucket
        (= one parquet row-group) at a time. Peak memory is
        O(chunk_rows + this bucket's duplicate entries), independent of
        job size. Rows never flushed (cancelled before running) fill as
        ``finish_reason="cancelled"`` with null outputs — same rule as
        the old in-memory assembly. Does NOT flip job status: callers
        update accounting first, then set SUCCEEDED (the
        results-before-status invariant holds either way because the
        final file only appears at the atomic rename below).

        ``on_chunk(df)`` sees each ordered bucket frame — accounting
        hooks (output-token counts) ride the same single pass. On a
        TRANSIENT I/O failure the whole pass retries from scratch
        (bounded, backed off), so ``on_chunk`` observers must reset
        when they see the bucket starting at row 0 again.
        """
        with host_leaf("finalize"):
            t0 = time.monotonic()
            retry_transient(
                lambda: self._write_results_streamed_once(
                    job_id, num_rows, on_chunk
                ),
                attempts=self.io_retries,
                base=self.io_backoff,
                cap=self.io_backoff_cap,
                retry_on=(OSError,),
                on_retry=lambda attempt, delay, exc: (
                    self.append_failure_log(
                        job_id,
                        {"event": "io_retry", "site": "jobstore.finalize",
                         "attempt": attempt,
                         "error": f"{type(exc).__name__}: {exc}"},
                    )
                ),
                what=f"finalize[{job_id}]",
            )
            if telemetry.ENABLED:
                dt = time.monotonic() - t0
                telemetry.stage_observe("finalize", dt)
                telemetry.RECORDER.record(
                    "finalize", job_id, t0, dt, {"rows": num_rows}
                )

    def _write_results_streamed_once(
        self,
        job_id: str,
        num_rows: int,
        on_chunk=None,
    ) -> None:
        import pyarrow as pa
        import pyarrow.parquet as pq

        if faults.ACTIVE is not None:
            faults.inject("jobstore.finalize", job=job_id)
        schema = pa.schema(
            [
                ("row_id", pa.int64()),
                ("outputs", pa.string()),
                ("cumulative_logprobs", pa.float64()),
                ("gen_tokens", pa.int64()),
                ("finish_reason", pa.string()),
                ("error", pa.string()),
            ]
        )
        import numpy as np

        by_bucket: Dict[int, List[tuple]] = {}
        for bucket, seq, p in self._partial_chunks(job_id):
            by_bucket.setdefault(bucket, []).append((seq, p))
        legacy = self._legacy_partial(job_id)  # compat: one old-format
        #                                        file, loaded once
        n_buckets = max(
            1, (num_rows + self.chunk_rows - 1) // self.chunk_rows
        )
        tmp = self._dir(job_id) / "results.parquet.tmp"
        writer = pq.ParquetWriter(tmp, schema)
        try:
            for bucket in range(n_buckets):
                lo = bucket * self.chunk_rows
                hi = min(lo + self.chunk_rows, num_rows)
                frames: List[pd.DataFrame] = []
                if legacy is not None and len(legacy):
                    in_range = legacy[
                        (legacy["row_id"] >= lo) & (legacy["row_id"] < hi)
                    ]
                    if len(in_range):
                        frames.append(in_range)
                for _seq, p in sorted(by_bucket.get(bucket, ())):
                    chunk = self._read_chunk(job_id, p)
                    if chunk is not None:
                        frames.append(chunk)
                if frames:
                    df = pd.concat(frames, ignore_index=True)
                    missing = [
                        c
                        for c in self._GEN_COLS
                        if c not in self._GEN_BACKFILL
                        and c not in df.columns
                    ]
                    if missing:
                        # gen_tokens/error are backfillable (pre-upgrade
                        # partial rows lack them); anything else missing
                        # is a bug and must raise, not record nulls
                        raise ValueError(
                            f"partial rows for {job_id} lack columns "
                            f"{missing}"
                        )
                    if "gen_tokens" not in df.columns:
                        df = df.assign(gen_tokens=0)
                    if "error" not in df.columns:
                        df = df.assign(error=None)
                    sub = df.drop_duplicates(
                        subset="row_id", keep="last"
                    ).set_index("row_id").reindex(range(lo, hi))
                    never_ran = sub["finish_reason"].isna()
                    outputs = [
                        None if pd.isna(v) else v
                        for v in sub["outputs"].tolist()
                    ]
                    reasons = [
                        "cancelled" if m else r
                        for m, r in zip(
                            never_ran.tolist(),
                            sub["finish_reason"].tolist(),
                        )
                    ]
                    errors = [
                        None if (isinstance(v, float) and pd.isna(v))
                        or v is None
                        else str(v)
                        for v in sub["error"].tolist()
                    ]
                    logps = (
                        pd.to_numeric(
                            sub["cumulative_logprobs"], errors="coerce"
                        )
                        .fillna(0.0)
                        .to_numpy(np.float64)
                    )
                    gen_toks = (
                        pd.to_numeric(sub["gen_tokens"], errors="coerce")
                        .fillna(0)
                        .to_numpy(np.int64)
                    )
                else:
                    n = hi - lo
                    outputs = [None] * n
                    reasons = ["cancelled"] * n
                    errors = [None] * n
                    logps = np.zeros((n,), np.float64)
                    gen_toks = np.zeros((n,), np.int64)
                out = pd.DataFrame(
                    {
                        "row_id": np.arange(lo, hi, dtype=np.int64),
                        "outputs": outputs,
                        "cumulative_logprobs": logps,
                        "gen_tokens": gen_toks,
                        "finish_reason": reasons,
                        "error": errors,
                    }
                )
                if on_chunk is not None:
                    on_chunk(out)
                writer.write_table(
                    pa.Table.from_pandas(
                        out, schema=schema, preserve_index=False
                    )
                )
        finally:
            writer.close()
        tmp.replace(self._dir(job_id) / "results.parquet")

    def read_results(self, job_id: str) -> pd.DataFrame:
        path = self._dir(job_id) / "results.parquet"
        # status gate, not just file existence: results.parquet lands
        # (atomic rename) a few ms BEFORE the record flips to SUCCEEDED
        # (accounting updates sit between), and a concurrent reader must
        # not observe results on a still-RUNNING job — the public
        # contract is "SUCCEEDED implies results readable", never the
        # converse (caught by test_races' pre-terminal-results check)
        status = self.status(job_id)
        if status != JobStatus.SUCCEEDED or not path.exists():
            raise FileNotFoundError(
                f"Results for {job_id} not available (status={status.value})"
            )
        return pd.read_parquet(path)

    # -- quotas ----------------------------------------------------------
    def get_quotas(self) -> List[Dict[str, int]]:
        path = config_dir() / "quotas.json"
        if path.exists():
            try:
                return json.loads(path.read_text())
            except (OSError, ValueError) as e:
                logger.warning(
                    "quotas.json unreadable (%s); using default quotas", e
                )
        return [dict(q) for q in DEFAULT_QUOTAS]

    def validate_priority(
        self, priority: Any, quotas: Optional[List[Dict[str, int]]] = None
    ) -> int:
        """The submit-time ``job_priority`` gate: an int indexing the
        quota table, or :class:`InvalidPriority`. No clamping — a
        priority outside the table would otherwise silently inherit
        another level's quota AND queue position."""
        if quotas is None:
            quotas = self.get_quotas()
        try:
            p = int(priority)
        except (TypeError, ValueError):
            raise InvalidPriority(priority, len(quotas)) from None
        if not 0 <= p < len(quotas):
            raise InvalidPriority(priority, len(quotas))
        return p

    def check_quota(
        self, priority: int, num_rows: int, est_tokens: int
    ) -> Optional[str]:
        quotas = self.get_quotas()
        q = quotas[self.validate_priority(priority, quotas)]
        if num_rows > q["row_quota"]:
            return (
                f"Row count {num_rows} exceeds priority-{priority} quota "
                f"{q['row_quota']}"
            )
        if est_tokens > q["token_quota"]:
            return (
                f"Estimated tokens {est_tokens} exceed priority-{priority} "
                f"quota {q['token_quota']}"
            )
        return None
