"""Closed-loop batch clients: each submits a job, follows its progress
stream to the end, reads its rows, and submits the next (a caller of
``so.infer`` waits for its DataFrame). With more rows outstanding than
the decode batch holds, the batch stays full.

Traffic file keys: ``clients``; ``rows_per_job`` (an int, or
``{"of_decode_batch": f}`` so one file serves configurations with
different batches); ``system_prompt``, ``output_schema``, ``sampling``
(as the SDK sends them); ``max_new_tokens_cycle`` (client c's j-th job
takes entry (c + j) mod len, so co-batched jobs end at different times
and the batch does not turn over in lockstep); ``prompt_chars`` (a fixed
heavy-tailed pool of prompt lengths, see ``stats.lognormal_pool``; every
job holds the whole pool and the seed only orders it); ``warm`` (``groups`` of ``rows`` prompts of
``chars`` characters, one small job each, one per prefill shape the
window will use); ``lead_in_s`` (long enough for the batch to turn over
once, so the window opens on a fragmented pool and desynchronised rows).
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List

from .. import schema_check
from ..stats import pool_from_spec
from .textgen import text_of_length

FINISHED_OK = ("stop", "length", "schema_complete")
RUNNING = ("QUEUED", "STARTING", "RUNNING", "CANCELLING")


def build(traffic: Dict[str, Any], env) -> "BatchJobs":
    return BatchJobs(traffic, env)


class BatchJobs:
    def __init__(self, traffic: Dict[str, Any], env):
        self.traffic, self.env = traffic, env
        self.lead_in_s = float(traffic.get("lead_in_s", 0.0))
        self.drain_s = float(traffic.get("drain_s", 10.0))
        pool = pool_from_spec(traffic["prompt_chars"])
        # every job holds the same sizes (the pool, repeated to the
        # job's rows); the seed only orders them, so every seed and
        # every job offers the same work
        self.pool = pool
        self._order_rng = env.rng("batch-order")
        self._lock = threading.Lock()
        self._text_rng = env.rng("batch-text")
        self._serial = 0
        rows = traffic["rows_per_job"]
        if isinstance(rows, dict):
            rows = max(1, int(rows["of_decode_batch"] * env.sut.decode_batch()))
        self.rows_per_job = int(rows)
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._live: Dict[str, Dict[str, Any]] = {}

    # -- inputs ------------------------------------------------------------

    def _inputs(self, lengths: List[int]) -> List[str]:
        with self._lock:
            out = []
            for n in lengths:
                self._serial += 1
                out.append(text_of_length(
                    self._text_rng, n, head=f"Review {self._serial}:"
                ))
            return out

    def _next_lengths(self, n: int) -> List[int]:
        with self._lock:
            reps = -(-n // len(self.pool))
            sizes = (self.pool * reps)[:n]
            return [sizes[i] for i in self._order_rng.permutation(n)]

    # -- one job, to its end -------------------------------------------------

    def run_job(self, inputs: List[str], max_new: int, warm: bool) -> Dict:
        env, t = self.env, self.traffic
        sampling = dict(t.get("sampling") or {})
        sampling["max_new_tokens"] = int(max_new)
        rec = env.log.add_job({
            "job_id": None, "submitted": time.monotonic(), "ended": None,
            "status": None, "rows": len(inputs), "max_new_tokens": max_new,
            "schema": t.get("output_schema"), "warm": warm, "problems": [],
            "length_rows": 0,
        })
        job_id = env.sut.submit_job(
            inputs, sampling=sampling, system_prompt=t.get("system_prompt"),
            output_schema=t.get("output_schema"),
            name=t.get("job_name", "perfbench"),
        )
        rec["job_id"] = job_id
        with self._lock:
            self._live[job_id] = rec
        try:
            for upd in env.sut.job_updates(job_id):
                now = time.monotonic()
                kind, res = upd.get("update_type"), upd.get("result")
                if kind == "tokens" and isinstance(res, dict):
                    env.log.tokens(
                        now, job_id, res.get("output_tokens", 0),
                        res.get("input_tokens", 0),
                    )
            rec["status"] = self._settled_status(job_id)
            rec["ended"] = time.monotonic()
            if rec["status"] == "SUCCEEDED":
                rec["max_new_tokens"] = env.sut.job_token_cap(job_id, max_new)
                self._check_rows(rec, env.sut.job_rows(job_id))
            elif rec["status"] != "CANCELLED":
                rec["problems"].append(
                    f"job {job_id} ended {rec['status']}: "
                    f"{env.sut.job_failure(job_id)}"
                )
        finally:
            with self._lock:
                self._live.pop(job_id, None)
        return rec

    def _settled_status(self, job_id: str) -> str:
        """The progress stream ends a moment before the record flips to
        its terminal state; wait for that, briefly."""
        deadline = time.monotonic() + 10.0
        status = self.env.sut.job_status(job_id)
        while status in RUNNING and time.monotonic() < deadline:
            time.sleep(0.02)
            status = self.env.sut.job_status(job_id)
        return status

    def _check_rows(self, rec: Dict, rows: List[Dict]) -> None:
        """Accounting and schemas (``correct`` checks 1 and 2): facts
        that hold for every seed and every interleaving."""
        job_id, cap, schema = rec["job_id"], rec["max_new_tokens"], rec["schema"]
        bad = rec["problems"]
        if len(rows) != rec["rows"]:
            bad.append(f"job {job_id}: {len(rows)} rows for {rec['rows']} inputs")
        for row in rows:
            where = f"job {job_id} row {row['row_id']}"
            reason, n = row["finish_reason"], row["gen_tokens"]
            if row["error"] is not None or reason not in FINISHED_OK:
                bad.append(f"{where}: finish {reason!r}, error {row['error']!r}")
                continue
            # a stop token is stripped from the count, so a row that
            # stops at once has none; every other row has at least one
            least = 0 if reason == "stop" else 1
            if n is None or not least <= n <= cap:
                bad.append(f"{where}: gen_tokens {n} outside [{least}, {cap}]")
            if schema is None:
                continue
            if reason != "schema_complete":
                rec["length_rows"] += 1  # counted, not parsed
                continue
            try:
                value = json.loads(row["output"])
            except (TypeError, ValueError) as e:
                bad.append(f"{where}: schema_complete but not JSON ({e}): "
                           f"{str(row['output'])[:120]!r}")
                continue
            wrong = schema_check.violation(value, schema)
            if wrong:
                bad.append(f"{where}: violates its schema: {wrong}")

    # -- the generator's life ----------------------------------------------

    def warm(self) -> None:
        """One small job a prefill shape, one after another: a job of
        ``rows`` prompts of ``chars`` characters is admitted as one group
        and compiles (or loads) the program of that [rows, length]
        bucket."""
        w = self.traffic.get("warm") or {}
        cap = int(w.get("max_new_tokens", 9))
        for group in w.get("groups", []):
            lengths = [int(group["chars"])] * int(group["rows"])
            self.run_job(self._inputs(lengths), cap, True)

    def _client(self, index: int) -> None:
        cycle = self.traffic["max_new_tokens_cycle"]
        j = 0
        try:
            while not self._stop.is_set():
                lengths = self._next_lengths(self.rows_per_job)
                self.run_job(
                    self._inputs(lengths),
                    int(cycle[(index + j) % len(cycle)]), False,
                )
                j += 1
        except Exception as e:  # noqa: BLE001 - a dead client is a dead run
            self.env.log.fatal(f"batch client {index} died: {e!r}")

    def start(self, t0: float) -> None:
        for c in range(int(self.traffic["clients"])):
            th = threading.Thread(
                target=self._client, args=(c,), daemon=True,
                name=f"perfbench-batch-{c}",
            )
            th.start()
            self._threads.append(th)
            # stagger the first submits so jobs queue in a fixed order
            time.sleep(0.05)

    def stop(self, t_end: float) -> None:
        self._stop.set()
        deadline = t_end + self.drain_s
        for th in self._threads:
            th.join(timeout=max(deadline - time.monotonic(), 0.0))
        # a client caught inside a submit has no job to cancel yet: keep
        # cancelling what appears until every client has ended
        cancelled: set = set()
        give_up = time.monotonic() + 30.0
        while any(th.is_alive() for th in self._threads):
            with self._lock:
                running = [j for j in self._live if j not in cancelled]
            for job_id in running:
                self.env.sut.cancel_job(job_id)
                cancelled.add(job_id)
            if time.monotonic() >= give_up:
                break
            time.sleep(0.2)
        for th in self._threads:
            if th.is_alive():
                self.env.log.note(f"batch client {th.name} did not end")
