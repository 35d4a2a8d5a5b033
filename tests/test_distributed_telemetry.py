"""Distributed telemetry (telemetry/distributed.py, OBSERVABILITY.md
"Distributed telemetry" / "Doctor").

Covers the tentpole end to end at the channel level (no engines — the
full 2-process engine acceptance lives in test_dphost.py):

1. wire pieces — trace context versioning, worker shard bounds,
   registry snapshot/delta math, coordinator ingestion + federation
   (worker-labelled series, overflow collapse, prom-text validity);
2. a real coordinator/worker round over localhost with telemetry
   riding the channel, including graceful degradation against
   old-frame peers in BOTH directions;
3. the bottleneck doctor — verdict-list unit cases and the
   golden-pinned diagnosis of a deterministic merged document.
"""

import json
import threading
import time
from pathlib import Path

import pytest

from sutro_tpu import telemetry
from sutro_tpu.telemetry import distributed, doctor
from sutro_tpu.telemetry.registry import MetricsRegistry, snapshot_delta
from sutro_tpu.telemetry.spans import FlightRecorder, JobTelemetryStore

from tests.conftest import free_low_port as _free_port
from tests.test_telemetry import assert_valid_prometheus

DOCTOR_GOLDEN = Path(__file__).parent / "data" / "doctor_verdict.golden"


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset_for_tests()
    telemetry.set_enabled(True)
    yield
    telemetry.reset_for_tests()
    telemetry.set_enabled(True)


# ---------------------------------------------------------------------------
# wire pieces
# ---------------------------------------------------------------------------


def test_trace_context_versioned_and_disabled_off():
    ctx = distributed.trace_context("job-x", 3)
    assert ctx["v"] == distributed.WIRE_VERSION
    assert ctx["trace"] == "job-x/r3" and ctx["round"] == 3
    telemetry.set_enabled(False)
    assert distributed.trace_context("job-x", 4) is None


def test_worker_telemetry_rejects_foreign_wire_version():
    w = distributed.WorkerTelemetry("j", 1)
    assert w.begin({"v": distributed.WIRE_VERSION + 1}) is False
    assert w.payload() is None
    # no context at all (old coordinator) is equally inert
    w2 = distributed.WorkerTelemetry("j", 1)
    assert w2.begin(None) is False
    assert w2.payload() is None


def test_worker_payload_spans_bounded(monkeypatch):
    monkeypatch.setattr(distributed, "MAX_SHIP_SPANS", 16)
    rec = FlightRecorder(capacity=256)
    jobs = JobTelemetryStore()
    reg = MetricsRegistry()
    w = distributed.WorkerTelemetry(
        "j", 1, registry=reg, recorder=rec, jobs=jobs
    )
    assert w.begin(distributed.trace_context("j", 1)) is True
    for i in range(40):
        rec.record("accept", "j", time.monotonic(), 0.001, {"i": i})
    p = w.payload()
    # 40 spans + the dp_round envelope, capped at 16 newest
    assert len(p["spans"]) == 16
    assert p["spans_dropped"] == 25
    assert p["spans"][-1]["name"] == "dp_round"  # envelope recorded last
    assert p["v"] == distributed.WIRE_VERSION and p["rank"] == 1


def test_snapshot_delta_counters_hists_gauges():
    r = MetricsRegistry()
    c = r.counter("d_total", "x", labels=("k",))
    h = r.histogram("d_seconds", "x", buckets=(0.1, 1.0))
    g = r.gauge("d_gauge", "x")
    c.inc(5, "a")
    h.observe(0.05)
    g.set(1.0)
    before = r.export_snapshot()
    c.inc(2, "a")
    c.inc(1, "b")
    h.observe(5.0)
    g.set(42.0)
    d = snapshot_delta(before, r.export_snapshot())
    assert [["d_total", ["a"], 2.0], ["d_total", ["b"], 1.0]] == d[
        "counters"
    ]
    ((name, lv, acc),) = d["hists"]
    assert name == "d_seconds" and acc[-1] == 1.0  # one new observation
    assert ["d_gauge", [], 42.0] in d["gauges"]  # current value, not delta
    # a quiet registry ships an empty delta
    d2 = snapshot_delta(r.export_snapshot(), r.export_snapshot())
    assert d2["counters"] == [] and d2["hists"] == []


def test_ingest_remote_federates_with_worker_label():
    r = MetricsRegistry()
    c = r.counter("f_total", "x", labels=("k",))
    c.inc(10, "a")
    h = r.histogram("f_seconds", "x", buckets=(0.1, 1.0))
    h.observe(0.5)
    shard = {
        "counters": [["f_total", ["a"], 3.0]],
        "hists": [["f_seconds", [], [1.0, 0.0, 0.0, 0.05, 1.0]]],
        "gauges": [],
    }
    r.ingest_remote("1", shard)
    r.ingest_remote("1", shard)  # deltas ACCUMULATE per worker
    snap = r.collect()
    assert snap["f_total"]["labels"] == ["k", "worker"]
    assert snap["f_total"]["series"]["a,0"] == 10.0
    assert snap["f_total"]["series"]["a,1"] == 6.0
    assert snap["f_seconds"]["series"]["1"]["count"] == 2
    # fleet total = sum over worker series (prom convention)
    text = r.to_prometheus()
    assert_valid_prometheus(text)
    assert 'f_total{k="a",worker="0"} 10' in text
    assert 'f_total{k="a",worker="1"} 6' in text


def test_ingest_remote_skips_unknown_and_malformed():
    r = MetricsRegistry()
    r.counter("k_total", "x")
    r.ingest_remote(
        "1",
        {
            "counters": [
                ["unknown_total", [], 5.0],  # undeclared -> skipped
                ["k_total", ["extra"], 1.0],  # label arity mismatch
                ["k_total"],  # malformed triple
                ["k_total", [], 2.0],  # valid
            ],
            "hists": [["k_total", [], [1.0]]],  # wrong kind -> skipped
        },
    )
    snap = r.collect()
    assert snap["k_total"]["series"] == {"1": 2.0}


def test_ingest_remote_worker_cardinality_bounded():
    r = MetricsRegistry()
    r.counter("w_total", "x")
    for i in range(MetricsRegistry.MAX_WORKERS + 10):
        r.ingest_remote(str(i + 1), {"counters": [["w_total", [], 1.0]]})
    series = r.collect()["w_total"]["series"]
    assert "_overflow" in series
    assert series["_overflow"] == 10.0
    # bounded store: at most MAX_WORKERS + overflow + local
    assert len(series) <= MetricsRegistry.MAX_WORKERS + 2


def test_distributed_store_rounds_and_bounds():
    store = distributed.DistributedTelemetry(max_sections=4)
    assert store.next_round("j") == 1
    assert store.next_round("j") == 2
    payload = {
        "v": distributed.WIRE_VERSION,
        "rank": 1, "round": 1, "epoch_unix": telemetry.RECORDER.epoch_wall,
        "spans": [{"name": "accept", "t0_s": 1.0, "dur_s": 0.5}],
        "counters": {"rows_ok": 3},
        "registry": {},
    }
    assert store.ingest("j", 1, payload) is True
    # same (round, rank) replaces (a reconnect's retry), new rank adds
    assert store.ingest("j", 1, payload) is True
    assert store.ingest("j", 2, {**payload, "rank": 2}) is True
    secs = store.sections("j")
    assert [(s["round"], s["rank"]) for s in secs] == [(1, 1), (1, 2)]
    assert secs[0]["spans"][0]["t0_coord_s"] == pytest.approx(1.0, abs=1e-6)
    # wire-version drift and garbage degrade to False, never raise
    assert store.ingest("j", 3, {**payload, "v": 99}) is False
    assert store.ingest("j", 3, "not a dict") is False
    assert store.ingest("j", 3, {**payload, "round": "NaNsense"}) is False
    # section cap
    for rr in range(3, 9):
        store.ingest("j", rr, {**payload, "rank": rr})
    assert len(store.sections("j")) <= 4


# ---------------------------------------------------------------------------
# channel-level round with telemetry riding the frames
# ---------------------------------------------------------------------------


def _world(port):
    from sutro_tpu.engine.dphost import DPWorld

    return (
        DPWorld(rank=0, world=2, host="127.0.0.1", port=port),
        DPWorld(rank=1, world=2, host="127.0.0.1", port=port),
    )


def _reqs(n):
    import numpy as np

    from sutro_tpu.engine.scheduler import GenRequest

    return [
        GenRequest(
            row_id=i, prompt_ids=np.zeros(1, np.int32), max_new_tokens=1
        )
        for i in range(n)
    ]


def _res(row_id):
    from sutro_tpu.engine.scheduler import GenResult

    return GenResult(
        row_id=row_id, token_ids=[7], cumulative_logprob=-0.5,
        finish_reason="stop", input_tokens=1,
    )


def _run_round(worker_tele, tele_ctx, on_worker_tele, worker_spans=3):
    """One coordinator/worker round over localhost with stub shards;
    returns (outcome, merged row ids)."""
    from sutro_tpu.engine.dphost import (
        run_dp_coordinator,
        run_dp_worker,
        shard_requests,
    )

    port = _free_port()
    cw, ww = _world(port)
    reqs = _reqs(8)
    merged = {}

    def coord_shard(shard, on_result, on_progress, should_cancel):
        for q in shard:
            on_result(_res(q.row_id))
        return "completed"

    def worker_shard(shard, on_result, on_progress, should_cancel):
        for k in range(worker_spans):
            telemetry.RECORDER.record(
                "decode_window", "wjob", time.monotonic(), 0.004,
                {"batch": 8, "steps": 4, "avg_ctx": 64.0},
            )
        telemetry.TOKENIZE_ROWS_TOTAL.inc(float(len(shard)))
        for q in shard:
            on_result(_res(q.row_id))
        return "completed"

    out = {}

    def worker_main():
        out["w"] = run_dp_worker(
            ww, worker_shard, shard_requests(reqs, 1, 2),
            tele=worker_tele,
        )

    t = threading.Thread(target=worker_main)
    t.start()
    outcome = run_dp_coordinator(
        cw, coord_shard, shard_requests(reqs, 0, 2),
        on_result=lambda r: merged.__setitem__(r.row_id, r),
        tele_ctx=tele_ctx,
        on_worker_tele=on_worker_tele,
    )
    t.join(timeout=120)
    assert not t.is_alive()
    assert out["w"] == "completed"
    return outcome, set(merged)


def test_channel_round_ships_worker_shard():
    store = distributed.DistributedTelemetry()
    round_no = store.next_round("cjob")
    ctx = distributed.trace_context("cjob", round_no)
    got = []

    def on_worker_tele(rank, shard):
        got.append((rank, shard))
        store.ingest("cjob", rank, shard)

    outcome, merged = _run_round(
        distributed.WorkerTelemetry("wjob", 1), ctx, on_worker_tele
    )
    assert outcome == "completed" and merged == {0, 1, 2, 3, 4, 5, 6, 7}
    ((rank, shard),) = got
    assert rank == 1 and shard["trace"] == "cjob/r1"
    (sec,) = store.sections("cjob")
    names = [s["name"] for s in sec["spans"]]
    assert names.count("decode_window") == 3
    assert names[-1] == "dp_round"
    # the worker's registry delta federated into the live registry
    snap = telemetry.REGISTRY.collect()
    tok = snap["sutro_tokenize_rows_total"]
    assert tok["labels"][-1] == "worker"
    assert tok["series"]["1"] == 4.0
    # ingestion is itself observable
    assert snap["sutro_dp_events_total"]["series"]["tele_shard"] == 1
    assert_valid_prometheus(telemetry.REGISTRY.to_prometheus())


def test_channel_old_worker_degrades_to_partial_data():
    """Coordinator with telemetry vs a worker that ships nothing (old
    frame / SUTRO_TELEMETRY=0 there): the round completes, the document
    reports partial data and the doctor names the silent rank."""
    store = distributed.DistributedTelemetry()
    ctx = distributed.trace_context("cjob", store.next_round("cjob"))
    got = []
    outcome, merged = _run_round(None, ctx, lambda r, s: got.append(r))
    assert outcome == "completed" and len(merged) == 8
    assert got == [] and store.sections("cjob") == []
    doc = {
        "job_id": "cjob",
        "spans": [
            {"name": "dp_round", "t0_s": 0.0, "dur_s": 2.0,
             "attrs": {"world": 2}},
            {"name": "decode_window", "t0_s": 0.1, "dur_s": 1.5},
        ],
        "counters": {"rows_ok": 8},
    }
    diag = doctor.diagnose(doc)
    assert diag["partial"] is True and diag["missing_ranks"] == [1]
    assert any("rank(s) 1" in e for e in diag["evidence"])
    assert diag["verdict"] != "insufficient_data"


def test_channel_old_coordinator_worker_ships_nothing():
    """Worker with telemetry against a coordinator that sends no trace
    context (old frame): the worker's session stays inert and the round
    completes — no half-opened telemetry."""
    w = distributed.WorkerTelemetry("wjob", 1)
    outcome, merged = _run_round(w, None, None)
    assert outcome == "completed" and len(merged) == 8
    assert w.payload() is None


# ---------------------------------------------------------------------------
# doctor
# ---------------------------------------------------------------------------


def _span(name, t0, dur, **attrs):
    d = {"name": name, "job_id": "j", "t0_s": t0, "dur_s": dur}
    if attrs:
        d["attrs"] = attrs
    return d


_V5E = {
    "device_kind": "TPU v5 lite", "n_devices": 1,
    "param_bytes": 2_000_000_000, "n_params": 1_000_000_000,
    "num_layers": 24, "kv_heads": 8, "head_dim": 128,
    "kv_dtype_bytes": 2,
}


def test_doctor_straggler_worker():
    doc = {
        "job_id": "j",
        "spans": [
            _span("dp_round", 0.0, 10.0, world=3),
            _span("decode_window", 0.0, 2.0),
        ],
        "counters": {"rows_ok": 10},
        "workers": [
            {"rank": 1, "round": 1,
             "spans": [_span("decode_window", 0.0, 2.0)],
             "counters": {"rows_ok": 5}},
            {"rank": 2, "round": 1,
             "spans": [_span("decode_window", 0.0, 9.5)],
             "counters": {"rows_ok": 5}},
        ],
    }
    diag = doctor.diagnose(doc)
    assert diag["verdict"] == "straggler_worker"
    assert any("rank2" in e for e in diag["evidence"])
    assert diag["processes"]["rank2"]["wall_s"] == 9.5


def test_doctor_host_bound_admit():
    doc = {
        "job_id": "j",
        "spans": [
            _span("constraint_compile", 0.0, 4.0),
            _span("accept", 4.0, 1.0),
            _span("decode_window", 5.0, 1.0),
        ],
        "counters": {},
    }
    diag = doctor.diagnose(doc)
    assert diag["verdict"] == "host_bound_admit"
    assert any("constraint_compile" in e for e in diag["evidence"])


def test_doctor_io_bound():
    doc = {
        "job_id": "j",
        "spans": [
            _span("flush", 0.0, 3.0),
            _span("finalize", 3.0, 2.0),
            _span("decode_window", 5.0, 1.0),
            _span("tokenize", 6.0, 0.1),
        ],
        "counters": {},
    }
    assert doctor.diagnose(doc)["verdict"] == "io_bound"


def test_doctor_decode_below_roofline():
    # 8 rows x 4 steps in 80 ms => 400 tok/s on a v5e: far under the
    # HBM roofline for this byte budget
    doc = {
        "job_id": "j",
        "attrs": {"device": _V5E},
        "spans": [
            _span("decode_window", 0.0, 0.08, batch=8, steps=4,
                  avg_ctx=128.0)
            for _ in range(4)
        ],
        "counters": {"rows_ok": 8, "input_tokens": 1024,
                     "output_tokens": 256},
    }
    diag = doctor.diagnose(doc)
    assert diag["verdict"] == "decode_below_roofline"
    rl = diag["processes"]["rank0"]["roofline"]
    assert rl["graded_windows"] == 4
    assert rl["decode_pct_hbm_median"] < 40.0


def test_doctor_unknown_device_grades_omitted_not_fabricated():
    doc = {
        "job_id": "j",
        "attrs": {"device": {**_V5E, "device_kind": "cpu"}},
        "spans": [
            _span("decode_window", 0.0, 0.08, batch=8, steps=4)
        ],
        "counters": {},
    }
    diag = doctor.diagnose(doc)
    rl = diag["processes"]["rank0"]["roofline"]
    assert rl["graded_windows"] == 0 and "no roofline spec" in rl["reason"]
    assert diag["verdict"] == "healthy"


def test_doctor_golden_pinned():
    """THE deterministic merged document (2-worker dp job, straggling
    rank 2, graded v5e decode windows) and its diagnosis, pinned
    byte-for-byte. Regenerate with
    ``python tests/test_distributed_telemetry.py --regen-golden``."""
    assert DOCTOR_GOLDEN.exists(), (
        "golden missing (regen: python "
        "tests/test_distributed_telemetry.py --regen-golden)"
    )
    got = json.dumps(doctor.diagnose(**_golden_case()), indent=2) + "\n"
    assert got == DOCTOR_GOLDEN.read_text()


def _golden_case():
    doc = {
        "version": 2,
        "job_id": "job-golden",
        "counters": {"rows_ok": 23, "rows_quarantined": 1,
                     "input_tokens": 4800, "output_tokens": 1200},
        "attrs": {"device": dict(_V5E)},
        "spans": [
            _span("dp_round", 0.0, 8.0, world=3),
            _span("tokenize", 0.0, 0.2, rows=24),
            _span("prefill", 0.3, 0.5, tokens=1600, batch=8),
            _span("decode_window", 1.0, 0.05, batch=8, steps=16,
                  avg_ctx=220.0),
            _span("decode_window", 1.1, 0.05, batch=8, steps=16,
                  avg_ctx=236.0),
            _span("accept", 1.2, 0.01),
            _span("flush", 1.3, 0.02),
            _span("finalize", 7.5, 0.4),
        ],
        "workers": [
            {
                "rank": 1, "round": 1, "trace": "job-golden/r1",
                "epoch_unix": 100.0, "clock_offset_s": 0.25,
                "spans": [
                    _span("tokenize", 0.0, 0.2, rows=24),
                    _span("decode_window", 0.5, 0.05, batch=8,
                          steps=16, avg_ctx=228.0),
                    _span("dp_round", 0.0, 2.4, rank=1),
                ],
                "spans_dropped": 0,
                "counters": {"rows_ok": 8},
                "attrs": {"device": dict(_V5E)},
            },
            {
                "rank": 2, "round": 1, "trace": "job-golden/r1",
                "epoch_unix": 100.0, "clock_offset_s": -0.125,
                "spans": [
                    _span("tokenize", 0.0, 0.2, rows=24),
                    _span("decode_window", 0.5, 0.6, batch=8,
                          steps=16, avg_ctx=228.0),
                    _span("dp_round", 0.0, 7.9, rank=2),
                ],
                "spans_dropped": 0,
                "counters": {"rows_ok": 8},
                "attrs": {"device": dict(_V5E)},
            },
        ],
    }
    return {"doc": doc, "status": "SUCCEEDED", "num_rows": 24}


if __name__ == "__main__":
    import sys

    if "--regen-golden" in sys.argv:
        DOCTOR_GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        DOCTOR_GOLDEN.write_text(
            json.dumps(doctor.diagnose(**_golden_case()), indent=2)
            + "\n"
        )
        print(f"wrote {DOCTOR_GOLDEN}")
