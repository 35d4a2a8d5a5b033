"""Mixed-tenant chaos bench: the SLO enforcement control plane gate.

Two legs over the same adversarial workload — a noisy tenant flooding
the interactive tier (OpenAI ``user`` field = tenant) while a victim
tenant sends occasional requests and a third tenant runs a small batch
job through the same engine:

- **off** (``SUTRO_CONTROL=0``): no admission control. The flood
  starves the victim; the live monitor's STOCK rule set (GET /monitor,
  no bench-private thresholds) must take ``interactive_ttft_p99`` to
  ``firing``. This leg reproduces the failure mode the control plane
  exists for, asserted through the same surface an operator watches.
- **on** (token-bucket admission, ``rows=<small>`` per window): the
  noisy tenant is throttled to HTTP-429-shaped rejections after its
  bucket drains, the victim's own bucket keeps admitting, and the same
  stock rule must NEVER leave ``ok``/``pending``. The batch tenant's
  job must still complete with zero lost rows.

The off leg stops as soon as the rule fires (bounded by a timeout);
the on leg runs a fixed number of monitor ticks under identical
pressure. Writes BENCH_CONTROL.json and prints one JSON line per leg.
``--smoke`` forces the CPU-sized configuration (CI); on a chip the
same shape runs with a bigger flood.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

#: stock rule under test (telemetry/monitor.py DEFAULT_RULES)
RULE = "interactive_ttft_p99"


def _get_monitor(url: str) -> dict:
    with urllib.request.urlopen(f"{url}/monitor", timeout=5) as r:
        return json.loads(r.read().decode("utf-8"))["monitor"]


def _rule_view(doc: dict) -> dict:
    for r in doc.get("rules", []):
        if r.get("name") == RULE:
            return r
    raise AssertionError(f"stock rule {RULE!r} missing from /monitor")


def _fired_events(doc: dict) -> list:
    return [
        ev
        for ev in doc.get("alerts", {}).get("events", [])
        if ev.get("rule") == RULE and ev.get("state") == "firing"
    ]


class _Leg:
    """One engine + HTTP daemon + the mixed-tenant workload around it."""

    def __init__(self, name, control_env, control_spec, params):
        self.name = name
        self.p = params
        self.home = tempfile.mkdtemp(prefix=f"sutro-bench-control-{name}-")
        os.environ["SUTRO_HOME"] = self.home
        os.environ["SUTRO_TELEMETRY"] = "1"
        os.environ["SUTRO_MONITOR"] = "1"
        os.environ["SUTRO_MONITOR_INTERVAL"] = str(params["interval_s"])
        os.environ["SUTRO_MONITOR_WINDOW"] = str(params["window_s"])
        if control_env is None:
            os.environ.pop("SUTRO_CONTROL", None)
        else:
            os.environ["SUTRO_CONTROL"] = control_env

        from sutro_tpu.engine.api import LocalEngine
        from sutro_tpu.engine.config import EngineConfig
        from sutro_tpu.server import start_server_thread

        self.eng = LocalEngine(EngineConfig(control=control_spec, **params["ecfg"]))
        self.server, self.thread, self.url = start_server_thread(self.eng)
        self.gw = self.eng.gateway
        assert self.gw is not None, "interactive_slots must be > 0"
        self.stop = threading.Event()
        self.noisy_ok = 0
        self.noisy_429 = 0
        self.victim_ttft = []
        self.victim_429 = 0
        self._lock = threading.Lock()

    # -- traffic -------------------------------------------------------

    def _one(self, tenant: str, max_tokens: int):
        """One streamed chat completion; returns ('ok', ttft) or
        ('429', None). Any other gateway refusal propagates — the bench
        must not paper over an unexpected failure mode."""
        from sutro_tpu.serving import openai as oai
        from sutro_tpu.serving.gateway import GatewayRejected
        from sutro_tpu.serving.openai import parse_request

        body = {
            "model": self.p["model"],
            "messages": [
                {"role": "user", "content": f"[{tenant}] say something."}
            ],
            "max_tokens": max_tokens,
            "stream": True,
            "user": tenant,
        }
        try:
            ir = self.gw.submit(parse_request(body, chat=True))
        except GatewayRejected as e:
            if e.status == 429:
                return "429", None
            raise
        for _ in oai.iter_stream(ir, chat=True):
            pass
        return "ok", ir.channel.ttft_s()

    def _noisy_loop(self):
        while not self.stop.is_set():
            kind, _ = self._one("noisy", self.p["noisy_tokens"])
            with self._lock:
                if kind == "ok":
                    self.noisy_ok += 1
                else:
                    self.noisy_429 += 1
            if kind == "429":
                # throttled: don't spin on the empty bucket
                time.sleep(0.25)

    def _victim_loop(self):
        while not self.stop.is_set():
            kind, ttft = self._one("victim", self.p["victim_tokens"])
            with self._lock:
                if kind == "ok" and ttft is not None:
                    self.victim_ttft.append(ttft)
                elif kind == "429":
                    self.victim_429 += 1
            # occasional traffic, not a second flood
            self.stop.wait(self.p["victim_gap_s"])

    def run(self, until_fired: bool):
        """Drive the flood; return the final /monitor document.

        ``until_fired`` — off leg: stop as soon as the stock rule
        fires (assert it does within the timeout). on leg: run the
        configured number of ticks and assert it NEVER fires."""
        # compile the interactive path out of band: the first request's
        # multi-second JIT stall must not masquerade as starvation and
        # push the on leg's early TTFT window over the rule threshold
        self._one("warm", 4)
        threads = [
            threading.Thread(target=self._noisy_loop, daemon=True)
            for _ in range(self.p["noisy_threads"])
        ] + [threading.Thread(target=self._victim_loop, daemon=True)]
        for t in threads:
            t.start()

        # the batch tenant's job rides the same engine the whole leg
        batch_jid = self.eng.submit_batch_inference(
            {
                "model": self.p["model"],
                "inputs": [
                    f"[batcher] chaos row {i}"
                    for i in range(self.p["batch_rows"])
                ],
                "sampling_params": {
                    "max_new_tokens": 4,
                    "temperature": 0.0,
                },
                "tenant": "batcher",
            }
        )

        deadline = time.monotonic() + self.p["timeout_s"]
        fired = False
        doc = {}
        try:
            while time.monotonic() < deadline:
                doc = _get_monitor(self.url)
                if _fired_events(doc) or _rule_view(doc)["state"] == "firing":
                    fired = True
                    if until_fired:
                        break
                if (
                    not until_fired
                    and doc.get("ticks", 0) >= self.p["on_ticks"]
                ):
                    break
                time.sleep(0.5)
        finally:
            # stop the flood even when a poll assertion raises — the
            # teardown in close() must not race live request threads
            self.stop.set()
            for t in threads:
                t.join(timeout=30)

        from sutro_tpu.engine.jobstore import JobStatus

        st = JobStatus(self.eng.job_status(batch_jid))
        t0 = time.monotonic()
        while not st.is_terminal() and time.monotonic() - t0 < 120:
            time.sleep(0.2)
            st = JobStatus(self.eng.job_status(batch_jid))
        batch = {"status": st.value, "rows": None}
        if st == JobStatus.SUCCEEDED:
            df = self.eng.jobs.read_results(batch_jid)
            batch["rows"] = len(df)

        ctl = getattr(self.eng, "control", None)
        entry = {
            "fired": fired,
            "ticks": doc.get("ticks"),
            "rule_state": _rule_view(doc)["state"] if doc else None,
            "rule_value": _rule_view(doc)["value"] if doc else None,
            "noisy_ok": self.noisy_ok,
            "noisy_429": self.noisy_429,
            "victim_ok": len(self.victim_ttft),
            "victim_429": self.victim_429,
            "victim_ttft_p99_s": _pct(self.victim_ttft, 99),
            "batch": batch,
            "control": ctl.snapshot() if ctl is not None else None,
        }
        return entry, doc

    def close(self):
        self.stop.set()
        try:
            self.server.shutdown()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass
        self.eng.close(timeout=30)
        shutil.rmtree(self.home, ignore_errors=True)


def _pct(samples, q):
    if not samples:
        return None
    xs = sorted(samples)
    i = min(len(xs) - 1, max(0, round(q / 100.0 * (len(xs) - 1))))
    return round(xs[i], 4)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="CPU-sized flood (CI); also the default off-chip",
    )
    args = ap.parse_args()

    import jax

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    on_tpu = jax.default_backend() not in ("cpu",)
    smoke = args.smoke or not on_tpu

    if smoke:
        params = dict(
            model="tiny-dense",
            interval_s=0.25,
            window_s=15,
            # flood depth is the starvation lever: tiny-dense emits EOS
            # after a handful of tokens regardless of max_tokens, so the
            # CPU stub serves ~25-30 req/s — TTFT under flood is roughly
            # outstanding/throughput, and clearing the 5 s stock-rule
            # threshold needs ~150+ requests queued
            noisy_threads=192,
            noisy_tokens=96,
            victim_tokens=8,
            victim_gap_s=2.5,
            batch_rows=6,
            timeout_s=240.0,
            on_ticks=60,  # ~15 s of sustained pressure with control on
            ecfg=dict(
                kv_page_size=8,
                max_pages_per_seq=16,
                decode_batch_size=2,
                max_model_len=160,
                max_new_tokens=96,
                use_pallas=False,
                param_dtype="float32",
                activation_dtype="float32",
                interactive_slots=1,
            ),
        )
    else:
        params = dict(
            model=os.environ.get("SUTRO_E2E_MODEL", "qwen-3-0.6b"),
            interval_s=0.25,
            window_s=15,
            noisy_threads=64,
            noisy_tokens=128,
            victim_tokens=16,
            victim_gap_s=2.5,
            batch_rows=64,
            timeout_s=240.0,
            on_ticks=120,
            ecfg=dict(
                decode_batch_size=8,
                kv_page_size=64,
                max_pages_per_seq=8,
                max_model_len=512,
                max_new_tokens=128,
                interactive_slots=2,
            ),
        )

    # bucket sized so the victim's occasional traffic always fits
    # (per-tenant buckets: capacity 6 rows + 0.2 rows/s refill covers a
    # request every 2.5 s) while the flood drains "noisy"'s own bucket
    # in under a second
    control_spec = "rows=6,tokens=300000,wait=0,window=30"

    results = {}

    # -- leg 1: control off — reproduce the starvation -----------------
    leg = _Leg("off", "0", control_spec, params)
    try:
        assert leg.eng.control is None, "SUTRO_CONTROL=0 must win"
        entry, _doc = leg.run(until_fired=True)
    finally:
        leg.close()
    results["off"] = entry
    print(json.dumps({"off": entry}), flush=True)
    assert entry["fired"], (
        f"off leg: flood never took stock rule {RULE} to firing "
        f"within {params['timeout_s']}s — not a starvation workload"
    )
    assert entry["batch"]["rows"] == params["batch_rows"], (
        f"off leg lost batch rows: {entry['batch']}"
    )

    # -- leg 2: control on — same flood, rule must stay quiet ----------
    leg = _Leg("on", None, control_spec, params)
    try:
        assert leg.eng.control is not None and leg.eng.control.enabled
        entry, doc = leg.run(until_fired=False)
    finally:
        leg.close()
    results["on"] = entry
    print(json.dumps({"on": entry}), flush=True)
    assert not entry["fired"] and not _fired_events(doc), (
        f"on leg: stock rule {RULE} fired with admission control "
        f"enabled: {entry}"
    )
    assert entry["noisy_429"] > 0, (
        "on leg: the noisy tenant was never throttled — bucket too big "
        f"for the flood: {entry}"
    )
    assert entry["victim_429"] == 0, (
        f"on leg: the victim tenant was throttled: {entry}"
    )
    assert entry["batch"]["rows"] == params["batch_rows"], (
        f"on leg lost batch rows: {entry['batch']}"
    )

    results["grades"] = {
        "off_rule_fired": results["off"]["fired"],
        "on_rule_fired": results["on"]["fired"],
        "on_noisy_429": results["on"]["noisy_429"],
        "on_victim_ttft_p99_s": results["on"]["victim_ttft_p99_s"],
        "target": (
            f"{RULE} fires with SUTRO_CONTROL=0, never fires with "
            "admission control on; victim + batch tenants unharmed"
        ),
        "ok": True,
    }
    print(json.dumps({"grades": results["grades"]}), flush=True)

    out = {
        "backend": jax.default_backend(),
        "smoke": smoke,
        "control_spec": control_spec,
        "params": {k: v for k, v in params.items() if k != "ecfg"},
        "ecfg": params["ecfg"],
        "legs": results,
    }
    REPO.joinpath("BENCH_CONTROL.json").write_text(
        json.dumps(out, indent=2)
    )
    print(json.dumps({"bench_control": "written"}), flush=True)


if __name__ == "__main__":
    main()
