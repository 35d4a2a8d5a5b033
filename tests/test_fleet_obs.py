"""Fleet observability plane (OBSERVABILITY.md "Fleet observability"):
cross-replica trace propagation + stitching, federated /metrics under
the ``replica`` label, the fleet SLO monitor, and the trace-replay
harness.

Layout mirrors the plane's layers:

1. unit — trace stitching against canned transports (golden Perfetto
   export with pinned clocks, skew re-anchor clamp), federation delta/
   cache/gauge semantics, the telemetry-off zero-op contract, and the
   replay capture/synthesize/round-trip/driver pieces (no engines);
2. fleet-monitor units — FLEET_RULES fire and resolve on hand-driven
   ticks, alert events embed route-latency exemplar trace ids;
3. integration over TWO live engines behind a live router — the
   acceptance stitch (router route_pick→first_byte AND replica
   admit_gateway→decode_window in one timeline, no negative offsets),
   federated /metrics, monitor endpoints, /replay-log + CLI;
4. protocol skew both directions + chaos: ``fleet.replica_crash``
   fires AND resolves a stock rule on the live monitor.

Destructive tests build their OWN servers/routers around the shared
engines so the module fixture stays healthy (same discipline as
tests/test_fleet.py).
"""

import json
import sys
import threading
import time
from pathlib import Path

import pytest
import requests

from sutro_tpu import telemetry
from sutro_tpu.engine import faults
from sutro_tpu.engine.api import LocalEngine
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.fleet import frames
from sutro_tpu.fleet import replay as replay_mod
from sutro_tpu.fleet.membership import CLOSED
from sutro_tpu.fleet.obs import (
    FLEET_AGG,
    FLEET_RULES,
    FleetMonitor,
    FleetObservability,
)
from sutro_tpu.fleet.router import FleetRouter, start_fleet_thread
from sutro_tpu.server import EngineHTTPHandler, start_server_thread
from sutro_tpu.telemetry import traceexport
from sutro_tpu.telemetry.registry import MetricsRegistry

GOLDEN = Path(__file__).parent / "data" / "fleet_trace_export.golden"

pytestmark = pytest.mark.skipif(
    not telemetry.ENABLED, reason="fleet observability needs telemetry"
)


def _wait(pred, timeout=30.0, interval=0.05, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------
# 1a. stitching: golden export with pinned clocks + skew clamp
# ---------------------------------------------------------------------

#: replica-side trace half with PINNED clocks: created 4ms after the
#: router's trace on the replica's wall clock, so the stitcher must
#: re-anchor every replica span by +0.004s onto the router timeline
_REPLICA_CREATED_SKEW_S = 0.004


def _replica_half(created_unix):
    from sutro_tpu.telemetry.traces import TraceStore

    store = TraceStore()
    tr = store.start_trace(
        "tr-fr-1", "interactive", {"model": "tiny-dense"},
        t0_mono=500.0, created_unix=created_unix,
    )
    tr.add("admit_gateway", 500.0, 0.0004, {"slot": 0})
    tr.add("prefill", 500.0008, 0.003, {"tokens": 7})
    tr.add("decode_window", 500.004, 0.0025, {"steps": 4})
    tr.end("ok")
    return tr.to_doc()


def _golden_stitched_doc(skew_s=_REPLICA_CREATED_SKEW_S):
    rdoc = _replica_half(1700000000.0 + skew_s)

    def canned_send(method, url, timeout=None):
        assert method == "get" and url.endswith("/trace-doc/tr-fr-1")
        return frames.trace_doc_frame(1700000000.2, rdoc)

    obs = FleetObservability(send=canned_send)
    tid = obs.trace_begin(
        "interactive", {"kind": "chat", "model": "tiny-dense"},
        t0_mono=100.0, created_unix=1700000000.0,
    )
    assert tid == "tr-fr-1"
    obs.span(tid, "route_pick", 100.0, 0.0031, {"n_candidates": 2})
    obs.span(tid, "affinity_probe", 100.0005, 0.0018, {"n_healthy": 2})
    obs.annotate(tid, {"replica": "r1", "replica_url": "http://rb"})
    obs.span(
        tid, "upstream_connect", 100.0032, 0.0009,
        {"rid": "r1", "status": 200},
    )
    obs.event(tid, "first_byte", {"rid": "r1"}, t_mono=100.0125)
    obs.end(tid, "ok")
    return obs.stitch_trace(tid)


def test_stitched_export_matches_golden():
    assert GOLDEN.exists(), (
        "golden file missing (regen: python tests/test_fleet_obs.py "
        "--regen-golden)"
    )
    doc = _golden_stitched_doc()
    assert traceexport.render(
        traceexport.stitched_to_chrome(doc)
    ) == GOLDEN.read_text()


def test_stitched_doc_shape_and_reanchor():
    doc = _golden_stitched_doc()
    assert doc["kind"] == "fleet" and doc["trace_id"] == "tr-fr-1"
    procs = doc["processes"]
    assert [p["process"] for p in procs] == ["router", "replica r1"]
    assert procs[0]["role"] == "router" and procs[0]["t_off_s"] == 0.0
    assert procs[1]["t_off_s"] == _REPLICA_CREATED_SKEW_S
    merged = traceexport.stitched_spans(doc)
    names = [s["name"] for s in merged]
    assert {"route_pick", "first_byte", "admit_gateway",
            "decode_window"} <= set(names)
    # no negative offsets after re-anchoring: every span sits at or
    # after the router's request arrival, and the replica's admission
    # never renders before the router picked it
    assert all(s["t0_s"] >= 0.0 for s in merged)
    by_name = {s["name"]: s for s in merged}
    assert by_name["admit_gateway"]["t0_s"] >= by_name["route_pick"]["t0_s"]


def test_stitch_clamps_negative_clock_skew():
    """A replica whose wall clock runs BEHIND the router's can never
    push its spans before the request arrived: t_off clamps at 0."""
    doc = _golden_stitched_doc(skew_s=-0.25)
    assert doc["processes"][1]["t_off_s"] == 0.0
    assert all(
        s["t0_s"] >= 0.0 for s in traceexport.stitched_spans(doc)
    )


def test_stitch_degrades_to_router_only_when_replica_gone():
    def dead_send(method, url, timeout=None):
        raise OSError("connection refused")

    obs = FleetObservability(send=dead_send)
    tid = obs.trace_begin("interactive", t0_mono=1.0, created_unix=2.0)
    obs.span(tid, "route_pick", 1.0, 0.001)
    obs.annotate(tid, {"replica": "r0", "replica_url": "http://gone"})
    obs.end(tid, "error")
    doc = obs.stitch_trace(tid)
    assert [p["process"] for p in doc["processes"]] == ["router"]
    # junk instead of a trace-doc frame degrades identically
    obs2 = FleetObservability(send=lambda *a, **k: {"t": "nope"})
    tid2 = obs2.trace_begin("interactive", t0_mono=1.0, created_unix=2.0)
    obs2.annotate(tid2, {"replica": "r0", "replica_url": "http://old"})
    obs2.end(tid2)
    assert len(obs2.stitch_trace(tid2)["processes"]) == 1
    assert obs2.stitch_trace("tr-fr-404") is None


# ---------------------------------------------------------------------
# 1b. federation: delta / cache / label / gauge semantics
# ---------------------------------------------------------------------


class _FakeMembership:
    def __init__(self, rows):
        self.rows = rows

    def all(self):
        return list(self.rows)


def _snap_with_counter(n):
    """A replica-side snapshot carrying real global metric names (the
    mirror registry only admits metrics the router also declares)."""
    reg = MetricsRegistry()
    c = reg.counter(
        "sutro_interactive_requests_total", "requests",
        labels=("outcome",), max_series=8,
    )
    for _ in range(n):
        c.inc(1, "ok")
    g = reg.gauge("sutro_interactive_active", "in flight")
    g.set(3.0)
    return reg.export_snapshot()


def test_federate_delta_cache_and_gauge_exclusion():
    sent = []

    def canned_send(method, url, timeout=None):
        sent.append(url)
        return frames.metrics_snapshot_frame(0.0, canned_send.snap)

    canned_send.snap = _snap_with_counter(5)
    obs = FleetObservability(scrape_interval_s=10.0, send=canned_send)
    mem = _FakeMembership(
        [
            {"rid": "rA", "url": "http://a", "state": CLOSED,
             "fleet_obs": True},
            {"rid": "rOld", "url": "http://b", "state": CLOSED,
             "fleet_obs": False},  # pre-obs replica: never scraped
        ]
    )
    assert obs.federate(mem, now=100.0) == 1
    assert sent == ["http://a/metrics-snapshot"]

    def remote_counter(worker):
        shard = obs.registry._remote[worker]
        return sum(
            v for (n, _lv), v in shard["counters"].items()
            if n == "sutro_interactive_requests_total"
        )

    assert remote_counter("rA") == 5
    assert remote_counter(FLEET_AGG) == 5
    # within the scrape interval: cache hit, no upstream traffic
    assert obs.federate(mem, now=100.5) == 0
    assert len(sent) == 1
    # next interval ingests the DELTA (cumulative stays exact)
    canned_send.snap = _snap_with_counter(8)
    assert obs.federate(mem, now=111.0) == 1
    assert remote_counter("rA") == 8
    assert remote_counter(FLEET_AGG) == 8
    # gauges are NOT federated — a replica gauge is that process's
    # "now", and relabeling it would corrupt the router's own census
    # strings (sutro_fleet_replicas{state="healthy"} N stays exact)
    assert obs.registry._remote["rA"]["gauges"] == {}
    text = obs.registry.to_prometheus()
    assert 'replica="rA"' in text and 'replica="_fleet"' in text
    assert not any(
        "sutro_interactive_active" in ln and 'replica="' in ln
        for ln in text.splitlines()
    )


def test_telemetry_off_is_zero_op_and_zero_send(monkeypatch):
    def no_send(method, url, timeout=None):
        raise AssertionError("telemetry off must not touch the network")

    obs = FleetObservability(send=no_send)
    monkeypatch.setattr(telemetry, "ENABLED", False)
    tid = obs.trace_begin("interactive", {"kind": "chat"})
    assert tid is None
    # the whole surface accepts the None id silently
    obs.span(tid, "route_pick", 0.0, 0.001)
    obs.event(tid, "first_byte")
    obs.annotate(tid, {"replica": "r0"})
    obs.end(tid)
    obs.observe_route(0.001, "chat", trace_id=tid)
    obs.refresh_router_gauges({"n_healthy": 2, "replicas": []})
    mem = _FakeMembership(
        [{"rid": "rA", "url": "http://a", "state": CLOSED,
          "fleet_obs": True}]
    )
    assert obs.federate(mem, now=1e9) == 0
    assert len(obs.traces.ids()) == 0
    assert obs.route_latency_summary() is None


def test_observe_route_records_summary_and_exemplar():
    obs = FleetObservability(send=lambda *a, **k: None)
    obs.observe_route(0.002, "chat", trace_id="tr-fr-901")
    obs.observe_route(0.004, "completions", trace_id="tr-fr-902")
    summary = obs.route_latency_summary()
    assert summary["count"] == 2 and summary["p99_s"] > 0
    tids = {
        ex.get("trace_id")
        for ex in obs.registry.exemplars("sutro_fleet_route_seconds")
    }
    assert "tr-fr-901" in tids or "tr-fr-902" in tids


# ---------------------------------------------------------------------
# 1c. replay: capture, synthesis, file format, driver
# ---------------------------------------------------------------------


def test_synthetic_records_deterministic_round_robin():
    a = replay_mod.synthetic_records(n=8, n_sessions=4)
    b = replay_mod.synthetic_records(n=8, n_sessions=4)
    assert a == b
    # sessions interleave round-robin: consecutive turns of one
    # session are n_sessions arrivals apart (the predecessor's KV has
    # time to checkpoint before the follow-up turn replays)
    assert [r["session_id"] for r in a[:4]] == [
        "replay-sess-0", "replay-sess-1", "replay-sess-2",
        "replay-sess-3",
    ]
    assert a[4]["session_id"] == "replay-sess-0"
    offs = [r["arrival_offset_s"] for r in a]
    assert offs == sorted(offs) and offs[0] > 0
    assert all(r["body"]["session_id"] == r["session_id"] for r in a)


def test_records_from_traces_rebases_and_caps(tmp_path):
    from sutro_tpu.telemetry.traces import TraceStore

    store = TraceStore()
    body = {"model": "tiny-dense", "messages": [], "stream": True}
    store.start_trace(
        "tr-fr-2", "interactive",
        replay_mod.replay_attrs(body, True, True, 1000.5, 64),
    )
    store.start_trace(
        "tr-fr-1", "interactive",
        replay_mod.replay_attrs(body, True, True, 1000.2, 64),
    )
    # oversized body: captured as a record, but not replayable
    store.start_trace(
        "tr-fr-3", "interactive",
        replay_mod.replay_attrs(
            body, False, False, 1000.9,
            replay_mod.REPLAY_BODY_MAX_BYTES + 1,
        ),
    )
    # non-request trace (no arrival stamp) is ignored
    store.start_trace("tr-fr-4", "probe", {"kind": "probe"})
    recs = replay_mod.records_from_traces(store)
    assert [r["arrival_offset_s"] for r in recs] == [0.0, 0.3, 0.7]
    assert recs[0]["body"] == body and "body" not in recs[2]
    assert recs[2]["kind"] == "completions"
    path = tmp_path / "w.jsonl"
    replay_mod.dump_jsonl(recs, path)
    assert replay_mod.load_jsonl(path) == recs


def test_replay_driver_honors_arrivals_open_loop():
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    hits = []

    class Stub(BaseHTTPRequestHandler):
        def do_POST(self):
            hits.append((time.perf_counter(), self.path))
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            data = b'data: {"ok": true}\n\ndata: [DONE]\n\n'
            self.send_response(200)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, fmt, *args):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    recs = [
        {"arrival_offset_s": 0.0, "kind": "chat",
         "body": {"model": "m"}},
        {"arrival_offset_s": 0.4, "kind": "completions",
         "body": {"model": "m"}},
        {"arrival_offset_s": 0.5, "kind": "chat"},  # no body: skipped
    ]
    try:
        doc = replay_mod.replay(url, recs, speedup=2.0, timeout=30.0)
    finally:
        srv.shutdown()
        srv.server_close()
    assert doc["n"] == 3 and doc["sent"] == 2 and doc["ok"] == 2
    assert doc["skipped_no_body"] == 1 and doc["errors"] == []
    assert doc["ttft"]["count"] == 2 and doc["ttft"]["p99_s"] > 0
    paths = sorted(p for _, p in hits)
    assert paths == ["/v1/chat/completions", "/v1/completions"]
    # 0.4s offset at 2x replays ~0.2s after start, never before
    ts = sorted(t for t, _ in hits)
    assert ts[1] - ts[0] >= 0.15


# ---------------------------------------------------------------------
# 2. fleet monitor: rules fire and resolve on hand-driven ticks
# ---------------------------------------------------------------------


def test_fleet_rules_catalog_is_stable():
    names = {r.name for r in FLEET_RULES}
    assert names == {
        "fleet_ttft_p99", "fleet_failover_rate",
        "fleet_prefix_hit_floor", "fleet_replica_imbalance",
        "fleet_replicas_down",
    }
    assert all(r.workload == "fleet" for r in FLEET_RULES)


def test_fleet_monitor_fires_and_resolves_failover_rate():
    router = FleetRouter([], probe_interval=3600.0)
    mon = FleetMonitor(router, interval_s=0.05, window_s=0.4)
    # an exemplar on the route histogram BEFORE the alert fires: the
    # firing event must point at a concrete stitched timeline
    router.obs.observe_route(0.003, "chat", trace_id="tr-fr-7171")
    mon.tick()
    time.sleep(0.05)
    router.counters["failover_stream_error"] += 10
    # for_ticks=2 debounce: one breaching tick arms (pending), the
    # second fires — while the spike is still inside the window
    mon.tick()
    time.sleep(0.05)
    mon.tick()
    doc = mon.snapshot_doc()
    active = {a["name"] for a in doc["alerts"]["active"]}
    assert "fleet_failover_rate" in active
    fired = [
        e for e in doc["alerts"]["events"]
        if e["rule"] == "fleet_failover_rate" and e["state"] == "firing"
    ]
    assert fired and "tr-fr-7171" in fired[0]["exemplar_trace_ids"]
    # chaos over: once the spike ages out of the window, the rate
    # clears the hysteresis level and the rule resolves
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        time.sleep(0.1)
        mon.tick()
        doc = mon.snapshot_doc()
        active = {a["name"] for a in doc["alerts"]["active"]}
        if "fleet_failover_rate" not in active:
            break
    assert "fleet_failover_rate" not in active
    assert any(
        e["rule"] == "fleet_failover_rate" and e["state"] == "resolved"
        for e in doc["alerts"]["events"]
    )


def test_fleet_monitor_replicas_down_is_census_driven():
    """A dead replica pages even when the fleet is idle: the rule reads
    the membership census, not traffic."""
    router = FleetRouter(
        ["http://127.0.0.1:1"], probe_interval=3600.0
    )
    mon = FleetMonitor(router, interval_s=0.05, window_s=0.4)
    router.membership.note_probe_success(
        "r0", {"ready": True, "draining": False, "load": {}}
    )
    mon.tick()
    assert "fleet_replicas_down" not in {
        a["name"] for a in mon.snapshot_doc()["alerts"]["active"]
    }
    for _ in range(10):  # breaker opens past the fail threshold
        router.membership.note_probe_failure("r0")
    mon.tick()  # pending (for_ticks=2)
    time.sleep(0.05)
    mon.tick()  # firing
    doc = mon.snapshot_doc()
    assert doc["stats"]["n_unhealthy"] >= 1.0
    assert "fleet_replicas_down" in {
        a["name"] for a in doc["alerts"]["active"]
    }
    assert doc["verdicts"]["fleet"]["verdict"] != "healthy"


# ---------------------------------------------------------------------
# 3. integration: two live engines behind a live router
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet(tmp_path_factory, monkeypatch_module):
    home = tmp_path_factory.mktemp("fleet-obs-home")
    monkeypatch_module.setenv("SUTRO_HOME", str(home))
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", max_new_tokens=8,
        interactive_slots=2,
    )
    eng_a = LocalEngine(ecfg)
    eng_b = LocalEngine(ecfg)
    srv_a, _, url_a = start_server_thread(eng_a)
    srv_b, _, url_b = start_server_thread(eng_b)
    router, fsrv, _, furl = start_fleet_thread(
        [url_a, url_b], probe_interval=0.2,
        monitor_interval=0.25, monitor_window=3.0,
    )
    from sutro_tpu.sdk import Sutro

    sdk = Sutro(api_key="fleet-key", base_url=furl, backend="fleet")
    _wait(
        lambda: router.membership.snapshot()["n_healthy"] == 2,
        timeout=15, what="both replicas healthy",
    )

    class F:
        pass

    f = F()
    f.eng_a, f.eng_b = eng_a, eng_b
    f.url_a, f.url_b = url_a, url_b
    f.router, f.furl, f.sdk = router, furl, sdk
    f.home = str(home)
    yield f
    faults.clear()
    router.stop()
    fsrv.shutdown()
    srv_a.shutdown()
    srv_b.shutdown()
    eng_a.close(timeout=10)
    eng_b.close(timeout=10)


def _routed_chat(furl, content, session=None, stream=True):
    body = {
        "model": "tiny-dense",
        "messages": [{"role": "user", "content": content}],
        "max_tokens": 4,
        "temperature": 0,
        "stream": stream,
    }
    if session:
        body["session_id"] = session
    r = requests.post(
        furl + "/v1/chat/completions", json=body, stream=stream,
        timeout=120,
    )
    assert r.status_code == 200, r.text[:300]
    if stream:
        lines = [ln for ln in r.iter_lines() if ln]
        assert lines[-1] == b"data: [DONE]"
    return r


def test_stitched_trace_e2e_through_two_replica_fleet(fleet):
    """THE acceptance stitch: one request through the fleet yields a
    single timeline with router spans (route_pick → first_byte) AND
    replica spans (admit_gateway → decode_window), all offsets
    non-negative after wall-clock re-anchoring."""
    before = set(fleet.router.obs.traces.ids())
    _routed_chat(fleet.furl, "stitch me a timeline")
    new = [t for t in fleet.router.obs.traces.ids() if t not in before]
    assert len(new) == 1
    tid = new[0]
    assert tid.startswith("tr-fr-")
    _wait(
        lambda: fleet.router.obs.traces.get(tid).finished,
        timeout=10, what="router trace finished",
    )
    doc = fleet.router.obs.stitch_trace(tid)
    assert [p["process"] for p in doc["processes"]][0] == "router"
    assert len(doc["processes"]) == 2
    merged = traceexport.stitched_spans(doc)
    names = {s["name"] for s in merged}
    assert {"route_pick", "upstream_connect", "first_byte"} <= names
    assert {"admit_gateway", "decode_window"} <= names
    assert all(s["t0_s"] >= 0.0 for s in merged), merged
    # and the HTTP surface serves the same thing as raw Chrome JSON
    r = requests.get(f"{fleet.furl}/trace/{tid}", timeout=10)
    assert r.status_code == 200
    chrome = r.json()
    assert chrome["otherData"]["trace_id"] == tid
    procs = chrome["otherData"]["processes"]
    assert procs[0] == "router" and procs[1].startswith("replica r")
    assert requests.get(
        f"{fleet.furl}/trace/tr-fr-404404", timeout=10
    ).status_code == 404


def test_federated_metrics_replica_label_and_exemplars(fleet):
    _routed_chat(fleet.furl, "metrics fodder", stream=False)
    time.sleep(0.3)  # past the scrape-cache interval
    text = requests.get(fleet.furl + "/metrics", timeout=10).text
    # per-replica serving series next to the fleet aggregate
    ttft_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("sutro_interactive_ttft_seconds")
    ]
    assert any('replica="r0"' in ln or 'replica="r1"' in ln
               for ln in ttft_lines), ttft_lines[:5]
    assert any('replica="_fleet"' in ln for ln in ttft_lines)
    # the router's own series: route latency with exemplar trace ids
    assert "sutro_fleet_route_seconds" in text
    assert "tr-fr-" in text
    # census gauges stay NON-federated and exact
    assert 'sutro_fleet_replicas{state="healthy"} 2' in text


def test_fleet_snapshot_surfaces_probe_only_and_route_latency(fleet):
    doc = fleet.sdk.get_fleet()
    assert doc["probe_only_routes"] == 0
    lat = doc["route_latency"]
    assert lat is not None and lat["count"] >= 1 and lat["p99_s"] > 0


def test_fleet_monitor_endpoints_and_stream(fleet):
    _wait(
        lambda: fleet.router.monitor is not None
        and fleet.router.monitor.snapshot_doc()["ticks"] >= 1,
        timeout=15, what="first monitor tick",
    )
    doc = fleet.sdk.get_fleet_monitor()
    assert doc["running"] and doc["degraded"] is None
    assert {r["name"] for r in doc["rules"]} == {
        r.name for r in FLEET_RULES
    }
    assert doc["verdicts"]["fleet"]["verdict"] in (
        "healthy", "degraded", "down", "insufficient_data",
    )
    r = requests.get(
        fleet.furl + "/fleet-monitor/stream?ticks=2", stream=True,
        timeout=30,
    )
    assert r.status_code == 200
    recs = [json.loads(ln) for ln in r.iter_lines() if ln]
    assert len(recs) == 3 and recs[-1]["t"] == "end"
    assert recs[-1]["degraded"] is None


def test_replay_log_roundtrip_and_cli(fleet, tmp_path, monkeypatch):
    from click.testing import CliRunner

    from sutro_tpu import cli as cli_mod

    _routed_chat(
        fleet.furl, "record this turn", session="replay-capture-sess"
    )
    records = fleet.sdk.get_replay_log()
    assert records and all("arrival_offset_s" in r for r in records)
    withbody = [r for r in records if r.get("body")]
    assert withbody, "small chat bodies must be captured replayable"
    assert withbody[-1]["kind"] == "chat"
    runner = CliRunner()
    assert runner.invoke(
        cli_mod.cli, ["set-base-url", fleet.furl]
    ).exit_code == 0
    assert runner.invoke(
        cli_mod.cli, ["set-backend", "fleet"]
    ).exit_code == 0
    out_path = tmp_path / "captured.jsonl"
    out = runner.invoke(
        cli_mod.cli, ["replay", "record", "-o", str(out_path)]
    )
    assert out.exit_code == 0, out.output
    loaded = replay_mod.load_jsonl(out_path)
    assert [r.get("session_id") for r in loaded] == [
        r.get("session_id") for r in records
    ]
    # fleet status renders the new observability lines
    out = runner.invoke(cli_mod.cli, ["fleet", "status"])
    assert out.exit_code == 0, out.output
    assert "probe-only routes" in out.output
    assert "route latency" in out.output


# ---------------------------------------------------------------------
# 4a. protocol skew, both directions
# ---------------------------------------------------------------------


def test_skew_new_router_old_replica_degrades_not_crashes(fleet):
    """An old replica (no fleet-state/warm/obs endpoints) behind a new
    router: routes still work probe-only, the forwarded X-Sutro-Trace
    header is ignored harmlessly, /trace/{id} degrades to router-only
    lanes, and federation skips the replica without erroring."""
    eng = fleet.eng_b

    class LegacyHandler(EngineHTTPHandler):
        engine = eng

        def do_GET(self):  # noqa: N802
            head = self.path.split("?")[0].strip("/").partition("/")[0]
            if head in ("fleet-state", "metrics-snapshot", "trace-doc"):
                self._error(404, f"Unknown endpoint GET /{head}")
                return
            super().do_GET()

        def do_POST(self):  # noqa: N802
            head = self.path.split("?")[0].strip("/").partition("/")[0]
            if head == "fleet-warm":
                self._error(404, f"Unknown endpoint POST /{head}")
                return
            super().do_POST()

    from http.server import ThreadingHTTPServer

    srv = ThreadingHTTPServer(("127.0.0.1", 0), LegacyHandler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    legacy_url = f"http://127.0.0.1:{srv.server_address[1]}"
    router2, fsrv2, _, furl2 = start_fleet_thread(
        [legacy_url], probe_interval=0.2
    )
    try:
        _wait(
            lambda: router2.membership.snapshot()["n_healthy"] == 1,
            timeout=15, what="legacy replica probed healthy",
        )
        assert not router2.membership.get("r0").get("fleet_obs")
        _routed_chat(furl2, "legacy skew route", stream=False)
        assert router2.counters["probe_only_routes"] >= 1
        tid = router2.obs.traces.ids()[-1]
        r = requests.get(f"{furl2}/trace/{tid}", timeout=10)
        assert r.status_code == 200
        assert r.json()["otherData"]["processes"] == ["router"]
        # federation sweeps right past the pre-obs replica
        text = requests.get(furl2 + "/metrics", timeout=10).text
        assert 'sutro_fleet_replicas{state="healthy"} 1' in text
        assert 'replica="r0"' not in text
    finally:
        router2.stop()
        fsrv2.shutdown()
        srv.shutdown()
        srv.server_close()


def test_skew_old_router_new_replica_mints_own_trace(fleet):
    """The other direction: a router that predates the obs plane sends
    no X-Sutro-Trace — the replica mints its own trace id and all obs
    endpoints still answer. With the header, the replica ADOPTS the
    router's id so /trace-doc/{id} can serve the far half."""
    before = set(telemetry.TRACES.ids())
    body = {
        "model": "tiny-dense",
        "messages": [{"role": "user", "content": "old router turn"}],
        "max_tokens": 4,
        "temperature": 0,
    }
    r = requests.post(
        fleet.url_a + "/v1/chat/completions", json=body, timeout=120
    )
    assert r.status_code == 200
    minted = [t for t in telemetry.TRACES.ids() if t not in before]
    assert minted and not minted[0].startswith("tr-fr-")
    # adoption: a router-assigned id becomes the replica trace id
    ext = "tr-fr-987654"
    r = requests.post(
        fleet.url_a + "/v1/chat/completions", json=body,
        headers={"X-Sutro-Trace": ext}, timeout=120,
    )
    assert r.status_code == 200
    assert telemetry.TRACES.get(ext) is not None
    raw = requests.get(
        f"{fleet.url_a}/trace-doc/{ext}", timeout=10
    ).json()
    parsed = frames.parse_trace_doc(raw)
    assert parsed is not None and parsed["doc"]["trace_id"] == ext
    # and the snapshot endpoint the router federates from
    raw = requests.get(
        fleet.url_a + "/metrics-snapshot", timeout=10
    ).json()
    assert frames.parse_metrics_snapshot(raw) is not None


# ---------------------------------------------------------------------
# 4b. chaos: a stock rule fires AND resolves on the live monitor
# ---------------------------------------------------------------------


def test_chaos_replica_crash_fires_and_resolves_fleet_rule(fleet):
    """fleet.replica_crash mid-stream -> failover_stream_error spikes
    -> fleet_failover_rate fires on the live monitor (with exemplar
    trace ids pointing at stitched timelines); chaos ends -> the spike
    ages out of the window -> the rule RESOLVES. `sutro fleet watch`
    renders the firing frame."""
    from click.testing import CliRunner

    from sutro_tpu import cli as cli_mod

    srv, _, url = start_server_thread(fleet.eng_a)
    router2, fsrv2, _, furl2 = start_fleet_thread(
        [url], probe_interval=0.2, stall_timeout=10.0,
        monitor_interval=0.1, monitor_window=1.0,
    )
    # a tick scrapes every replica before it samples, and the crashed
    # one answers nothing until the prober opens its circuit: at the
    # stock 2 s timeout one tick outlasts the 1 s window, the spike is
    # in a single sample, and a rule needs two breaching ticks in a row
    # (for_ticks). A tick must fit the window several times over.
    router2.obs.scrape_timeout = 0.2
    try:
        _wait(
            lambda: router2.membership.snapshot()["n_healthy"] == 1,
            timeout=15, what="replica healthy",
        )
        _routed_chat(furl2, "warm the streamed path")
        faults.install(faults.parse_plan(json.dumps([
            {"site": "fleet.replica_crash", "kind": "crash",
             "job": "stream:", "nth": 3, "times": 1}
        ])))
        r = requests.post(
            furl2 + "/v1/chat/completions",
            json={
                "model": "tiny-dense",
                "messages": [
                    {"role": "user", "content": "stream then die"}
                ],
                "max_tokens": 8,
                "stream": True,
            },
            stream=True,
            timeout=(5, 60),
        )
        assert r.status_code == 200
        assert any(
            '"error"' in ln.decode() for ln in r.iter_lines() if ln
        )
        faults.clear()
        assert router2.counters["failover_stream_error"] == 1

        def monitor_doc():
            resp = requests.get(furl2 + "/fleet-monitor", timeout=10)
            assert resp.status_code == 200
            return resp.json()["fleet_monitor"]

        def transitions(state):
            # the event log, not ``active``: the rule is active for as
            # long as the spike is in the window (a second), and a poll
            # from a loaded machine can land on either side of that
            return [
                e for e in monitor_doc()["alerts"]["events"]
                if e["rule"] == "fleet_failover_rate"
                and e["state"] == state
            ]

        _wait(
            lambda: transitions("firing"),
            timeout=15, what="fleet_failover_rate firing",
        )
        fired = transitions("firing")
        assert fired[0]["exemplar_trace_ids"], fired
        assert all(
            t.startswith("tr-fr-")
            for t in fired[0]["exemplar_trace_ids"]
        )
        # the operator view of the firing frame
        runner = CliRunner()
        assert runner.invoke(
            cli_mod.cli, ["set-base-url", furl2]
        ).exit_code == 0
        assert runner.invoke(
            cli_mod.cli, ["set-backend", "fleet"]
        ).exit_code == 0
        out = runner.invoke(cli_mod.cli, ["fleet", "watch", "--once"])
        assert out.exit_code == 0, out.output
        assert "sutro fleet watch" in out.output
        assert "fleet_failover_rate" in out.output
        # chaos over: the rule must RESOLVE, not latch
        _wait(
            lambda: transitions("resolved"),
            timeout=20, what="fleet_failover_rate resolved",
        )
        assert "fleet_failover_rate" not in {
            a["name"] for a in monitor_doc()["alerts"]["active"]
        }
        out = runner.invoke(cli_mod.cli, ["fleet", "watch", "--once"])
        assert out.exit_code == 0, out.output
    finally:
        faults.clear()
        router2.stop()
        fsrv2.shutdown()
        srv.shutdown()
        srv.server_close()


if __name__ == "__main__":
    if "--regen-golden" in sys.argv:
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN.write_text(
            traceexport.render(
                traceexport.stitched_to_chrome(_golden_stitched_doc())
            )
        )
        print(f"wrote {GOLDEN}")
