"""graftlint (sutro_tpu.analysis): rule fixtures (true positive, true
negative, suppressed), the self-scan baseline gate, injection
sensitivity on the real tree, and the engine fixes the passes drove
(narrowed excepts, bounded teardown)."""

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from sutro_tpu.analysis import core
from sutro_tpu.analysis.callgraph import PackageIndex
from sutro_tpu.analysis.core import run_passes

REPO = Path(__file__).resolve().parent.parent
BASELINE = REPO / "sutro_tpu" / "analysis" / "baseline.json"


def scan(src: str, name: str = "m", path: str = "m.py"):
    idx = PackageIndex()
    idx.add_source(path, src, name)
    active, suppressed = core.apply_suppressions(idx, run_passes(idx))
    return active, suppressed


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- locks


def test_lock_order_inversion_flagged():
    active, _ = scan(
        """
import threading
class S:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()
    def f(self):
        with self.a_lock:
            with self.b_lock:
                pass
    def g(self):
        with self.b_lock:
            self.h()
    def h(self):
        with self.a_lock:
            pass
"""
    )
    assert "lock-order" in rules_of(active)
    (f,) = [f for f in active if f.rule == "lock-order"]
    assert "S.a_lock" in f.message and "S.b_lock" in f.message


def test_consistent_lock_order_clean():
    active, _ = scan(
        """
import threading
class S:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()
    def f(self):
        with self.a_lock:
            with self.b_lock:
                pass
    def g(self):
        with self.a_lock:
            with self.b_lock:
                pass
"""
    )
    assert "lock-order" not in rules_of(active)


def test_cross_function_inversion_on_shared_object():
    active, _ = scan(
        """
import threading
class Bus:
    def __init__(self):
        self._lock = threading.Lock()
    def a(self, jm):
        with self._lock:
            with jm.lock:
                pass
    def b(self, jm):
        with jm.lock:
            with self._lock:
                pass
"""
    )
    assert "lock-order" in rules_of(active)


def test_blocking_call_under_lock_direct_and_interprocedural():
    active, _ = scan(
        """
import threading, time
def helper():
    time.sleep(1)
def f():
    lock = threading.Lock()
    with lock:
        helper()
"""
    )
    found = [f for f in active if f.rule == "lock-blocking-call"]
    assert found and "time.sleep" in found[0].message
    assert "call chain" in found[0].message


def test_blocking_call_outside_lock_clean():
    active, _ = scan(
        """
import threading, time
def f():
    lock = threading.Lock()
    with lock:
        pass
    time.sleep(1)
"""
    )
    assert "lock-blocking-call" not in rules_of(active)


def test_blocking_call_suppressed():
    active, suppressed = scan(
        """
import threading, time
def f():
    lock = threading.Lock()
    with lock:
        time.sleep(1)  # graftlint: disable=lock-blocking-call
"""
    )
    assert "lock-blocking-call" not in rules_of(active)
    assert "lock-blocking-call" in rules_of(suppressed)


def test_thread_join_under_lock_blocks_string_join_does_not():
    active, _ = scan(
        """
import threading
def f():
    lock = threading.Lock()
    t = threading.Thread(target=f, daemon=True)
    t.start()
    with lock:
        t.join(timeout=5)
        s = ",".join(["a", "b"])
"""
    )
    found = [f for f in active if f.rule == "lock-blocking-call"]
    assert len(found) == 1 and "t.join" in found[0].message


def test_callback_under_lock_flagged_and_clean_outside():
    active, _ = scan(
        """
import threading
def f(on_result):
    lock = threading.Lock()
    with lock:
        on_result(1)
    on_result(2)
"""
    )
    found = [f for f in active if f.rule == "lock-callback"]
    assert len(found) == 1 and found[0].line == 6


def test_reentrant_lock_acquisition_flagged_rlock_clean():
    active, _ = scan(
        """
import threading
def bad():
    lock = threading.Lock()
    with lock:
        with lock:
            pass
def fine():
    r = threading.RLock()
    with r:
        with r:
            pass
"""
    )
    found = [f for f in active if f.rule == "lock-reentrant"]
    assert len(found) == 1 and "bad.lock" in found[0].message


def test_nested_def_under_lock_not_treated_as_running():
    active, _ = scan(
        """
import threading, time
def f():
    lock = threading.Lock()
    with lock:
        def later():
            time.sleep(1)
        return later
"""
    )
    assert "lock-blocking-call" not in rules_of(active)


# -------------------------------------------------------------- jitpure


def test_jit_host_sync_flagged():
    active, _ = scan(
        """
import functools
import jax
import numpy as np
@functools.partial(jax.jit, static_argnames=("n",))
def step(x, n):
    y = np.asarray(x)
    m = int(n)
    k = float(x)
    return y
"""
    )
    msgs = [f.message for f in active if f.rule == "jit-host-sync"]
    assert any("np.asarray" in m for m in msgs)
    assert any("float(x)" in m for m in msgs)  # traced param
    assert not any("int(n)" in m for m in msgs)  # static param


def test_numpy_outside_jit_clean():
    active, _ = scan(
        """
import numpy as np
def host_side(x):
    return np.asarray(x)
"""
    )
    assert "jit-host-sync" not in rules_of(active)


def test_pallas_kernel_nondeterminism_flagged():
    active, _ = scan(
        """
import functools
import time
from jax.experimental import pallas as pl
def _kernel(x_ref, o_ref):
    t = time.time()
    o_ref[...] = x_ref[...]
def op(x):
    k = functools.partial(_kernel)
    return pl.pallas_call(k)(x)
"""
    )
    assert "jit-nondeterminism" in rules_of(active)


def test_sched_nondeterminism_flagged_monotonic_clean():
    active, _ = scan(
        """
import time
class ContinuousBatcher:
    def run_multi(self, jobs):
        self._step()
    def _step(self):
        a = time.monotonic()
        b = time.time()
        return a, b
""",
        name="engine.scheduler",
        path="engine/scheduler.py",
    )
    found = [f for f in active if f.rule == "sched-nondeterminism"]
    assert len(found) == 1 and "time.time" in found[0].message


def test_sched_rule_scoped_to_scheduler_modules():
    active, _ = scan(
        """
import time
class ContinuousBatcher:
    def run_multi(self, jobs):
        return time.time()
""",
        name="engine.other",
        path="engine/other.py",
    )
    assert "sched-nondeterminism" not in rules_of(active)


# -------------------------------------------------------------- hygiene


def test_thread_hygiene_matrix():
    active, _ = scan(
        """
import threading
def f():
    a = threading.Thread(target=f, daemon=True)
    a.start()
    b = threading.Thread(target=f)
    b.start()
    b.join(timeout=5)
    c = threading.Thread(target=f)
    c.start()
    c.join()
    d = threading.Thread(target=f)
    d.start()
"""
    )
    by_rule = {}
    for f in active:
        by_rule.setdefault(f.rule, []).append(f)
    assert [f.key for f in by_rule.get("thread-unbounded-join", [])] == [
        "c"
    ]
    assert [f.key for f in by_rule.get("thread-unjoined", [])] == ["d"]


def test_silent_except_shapes():
    active, suppressed = scan(
        """
import logging
logger = logging.getLogger(__name__)
def swallow_pass():
    try:
        pass
    except Exception:
        pass
def swallow_default():
    try:
        pass
    except Exception:
        return {}
def narrowed_ok():
    try:
        pass
    except ValueError:
        pass
def logged_ok():
    try:
        pass
    except Exception:
        logger.warning("x")
def blessed():
    try:
        pass
    except Exception:  # graftlint: disable=silent-except
        pass
"""
    )
    silent = [f for f in active if f.rule == "silent-except"]
    assert {f.symbol.split(":")[-1] for f in silent} == {
        "swallow_pass",
        "swallow_default",
    }
    assert "silent-except" in rules_of(suppressed)


def test_unbounded_retry_matrix():
    active, suppressed = scan(
        """
import time
def unbounded_constant_sleep(op):
    while True:
        try:
            return op()
        except OSError:
            time.sleep(1)
def bounded_no_backoff(op):
    for attempt in range(5):
        try:
            return op()
        except OSError:
            time.sleep(1)
def bounded_backoff_ok(op):
    for attempt in range(5):
        try:
            return op()
        except OSError:
            time.sleep(0.1 * 2 ** attempt)
def deadline_guard_ok(op, delay):
    deadline = time.monotonic() + 5
    while True:
        try:
            return op()
        except OSError:
            if time.monotonic() >= deadline:
                raise
            delay *= 2
            time.sleep(delay)
def service_loop_not_retry(q):
    while True:
        try:
            q.get()
        except Exception:
            q.log()
def terminal_handler_not_retry(op):
    while True:
        try:
            return op()
        except OSError:
            raise
def blessed(op):
    while True:  # graftlint: disable=unbounded-retry
        try:
            return op()
        except OSError:
            time.sleep(1)
"""
    )
    found = {
        f.symbol.split(":")[-1]: f
        for f in active
        if f.rule == "unbounded-retry"
    }
    assert set(found) == {
        "unbounded_constant_sleep", "bounded_no_backoff"
    }, found
    assert "bound" in found["unbounded_constant_sleep"].key
    assert "backoff" in found["bounded_no_backoff"].key
    assert "unbounded-retry" in rules_of(suppressed)


def test_unbounded_retry_engine_fixes_hold():
    """The engine's own retry loops must satisfy the rule they drove:
    faults.retry_transient (bounded + exponential backoff) and the dp
    worker reconnect loop (deadline-bounded + backoff)."""
    idx = PackageIndex()
    for rel in ("engine/faults.py", "engine/dphost.py"):
        p = REPO / "sutro_tpu" / rel
        idx.add_file(p, rel)
    active, _ = core.apply_suppressions(idx, run_passes(idx))
    assert "unbounded-retry" not in rules_of(active), [
        f.render() for f in active
    ]


# -------------------------------------- baseline & suppression mechanics


def test_baseline_count_semantics():
    src_two = """
def f():
    try:
        pass
    except Exception:
        pass
    try:
        pass
    except Exception:
        pass
"""
    active, _ = scan(src_two)
    base = core.baseline_counts(active)
    new, stale = core.compare_baseline(active, base)
    assert not new and not stale
    # a third identical finding in the same function is NEW
    active3, _ = scan(
        src_two
        + """
    try:
        pass
    except Exception:
        pass
"""
    )
    new, _ = core.compare_baseline(active3, base)
    assert len(new) == 1


# ------------------------------------------------- self-scan & CLI gate


def test_self_scan_matches_committed_baseline():
    active, _suppressed, _ = core.analyze([str(REPO / "sutro_tpu")])
    # findings are path-keyed relative to the repo root in CI; re-key
    # the absolute scan the same way
    for f in active:
        f.path = str(Path(f.path).relative_to(REPO).as_posix())
    baseline = core.load_baseline(BASELINE)
    new, stale = core.compare_baseline(active, baseline)
    assert not new, [f.render() for f in new]
    assert not stale, stale
    # pin the accepted-debt count: growing it needs a conscious
    # baseline regeneration in the same commit
    assert len(active) == sum(baseline.values()) == 16


def run_cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "sutro_tpu.analysis", *args],
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_cli_gate_green_on_tree():
    res = run_cli(["sutro_tpu"], cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 new" in res.stdout


def test_cli_unknown_rule_and_missing_path():
    assert run_cli(["--rules", "nope"], cwd=REPO).returncode == 2
    assert run_cli(["no/such/dir"], cwd=REPO).returncode == 2


def _copy_tree(tmp_path: Path) -> Path:
    dst = tmp_path / "sutro_tpu"
    shutil.copytree(
        REPO / "sutro_tpu",
        dst,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    return dst


def test_injected_wall_clock_in_decode_path_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    sched = dst / "engine" / "scheduler.py"
    src = sched.read_text()
    anchor = "self._prep_pump(order)"
    assert anchor in src
    src = src.replace(
        anchor, anchor + "\n                _wall = time.time()", 1
    )
    sched.write_text(src)
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "sched-nondeterminism" in res.stdout


def test_injected_lock_inversion_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    metrics = dst / "engine" / "metrics.py"
    metrics.write_text(
        metrics.read_text()
        + """

def _injected_a(bus, jm):
    with bus._lock:
        with jm.lock:
            pass


def _injected_b(bus, jm):
    with jm.lock:
        with bus._lock:
            pass
"""
    )
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "lock-order" in res.stdout


def test_write_baseline_roundtrip(tmp_path):
    dst = _copy_tree(tmp_path)
    bl = tmp_path / "bl.json"
    res = run_cli(
        ["sutro_tpu", "--baseline", str(bl), "--write-baseline"],
        cwd=tmp_path,
    )
    assert res.returncode == 0
    data = json.loads(bl.read_text())
    assert data["tool"] == "graftlint" and data["counts"]
    res = run_cli(["sutro_tpu", "--baseline", str(bl)], cwd=tmp_path)
    assert res.returncode == 0


def test_json_report_shape():
    res = run_cli(
        ["sutro_tpu", "--no-baseline", "--format", "json"], cwd=REPO
    )
    assert res.returncode == 1  # findings exist without a baseline
    data = json.loads(res.stdout)
    assert data["tool"] == "graftlint"
    assert all(
        {"rule", "path", "line", "message", "fingerprint"}
        <= set(f)
        for f in data["findings"]
    )


# ----------------------------------------- engine fixes the pass drove


def test_datasets_corrupt_meta_logged_not_swallowed(tmp_path, caplog):
    from sutro_tpu.engine.datasets import DatasetStore

    store = DatasetStore(root=tmp_path)
    ds = store.create()
    (tmp_path / ds / ".meta.json").write_text("{not json")
    with caplog.at_level("WARNING", logger="sutro_tpu.engine.datasets"):
        listed = store.list_datasets()
    assert [d["dataset_id"] for d in listed] == [ds]
    assert any("unreadable .meta.json" in r.message for r in caplog.records)


def test_datasets_bad_schema_file_logged(tmp_path, caplog):
    from sutro_tpu.engine.datasets import DatasetStore

    store = DatasetStore(root=tmp_path)
    ds = store.create()
    (tmp_path / ds / "broken.parquet").write_bytes(b"not a parquet")
    with caplog.at_level("WARNING", logger="sutro_tpu.engine.datasets"):
        listed = store.list_datasets()
    assert listed[0]["schema"] == {}
    assert any("cannot read parquet schema" in r.message for r in caplog.records)


def test_jobstore_corrupt_record_skipped_with_log(tmp_path, caplog):
    from sutro_tpu.engine.jobstore import JobStore

    store = JobStore(root=tmp_path)
    good = store.create(model="m", num_rows=1)
    bad = tmp_path / "job-deadbeef"
    bad.mkdir()
    (bad / "record.json").write_text("{torn")
    with caplog.at_level("WARNING", logger="sutro_tpu.engine.jobstore"):
        listed = store.list_jobs()
    assert [r["job_id"] for r in listed] == [good.job_id]
    assert any("unreadable job record" in r.message for r in caplog.records)


def test_fsm_cpp_failure_classified_and_fallback_works(monkeypatch, caplog):
    import sutro_tpu.engine.constrain.cpp as cpp_mod
    from sutro_tpu.engine.constrain import TokenTable, compile_schema
    from sutro_tpu.engine.constrain.fsm import MaskCache
    from sutro_tpu.engine.tokenizer import ByteTokenizer

    def boom(*a, **k):
        raise RuntimeError("simulated native failure")

    monkeypatch.setattr(cpp_mod, "CppMasker", boom)
    tok = ByteTokenizer(vocab_size=512)
    nfa = compile_schema(
        {
            "type": "object",
            "properties": {"x": {"type": "integer"}},
            "required": ["x"],
        }
    )
    with caplog.at_level("DEBUG", logger="sutro_tpu.engine.constrain.fsm"):
        cache = MaskCache(nfa, TokenTable(tok))
    assert cache._cpp is None
    assert any(
        "CppMasker init failed" in r.message for r in caplog.records
    )
    mask = cache.mask(nfa.initial())
    assert mask.any()  # pure-python walk still serves masks


def test_read_results_gated_on_terminal_status(tmp_path):
    """The finalize window (results.parquet renamed, SUCCEEDED not yet
    flipped) must be invisible: results serve only at SUCCEEDED."""
    import pandas as pd

    from sutro_tpu.engine.jobstore import JobStore
    from sutro_tpu.interfaces import JobStatus

    store = JobStore(root=tmp_path)
    rec = store.create(model="m", num_rows=1)
    store.set_status(rec.job_id, JobStatus.RUNNING)
    pd.DataFrame({"row_id": [0], "outputs": ["x"]}).to_parquet(
        tmp_path / rec.job_id / "results.parquet"
    )
    with pytest.raises(FileNotFoundError, match="status=RUNNING"):
        store.read_results(rec.job_id)
    store.set_status(rec.job_id, JobStatus.SUCCEEDED)
    assert store.read_results(rec.job_id)["outputs"].tolist() == ["x"]


def test_engine_close_joins_worker(tmp_path, monkeypatch):
    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    from sutro_tpu.engine.api import LocalEngine
    from sutro_tpu.engine.config import EngineConfig

    eng = LocalEngine(EngineConfig())
    assert eng._worker.is_alive()
    assert eng.close(timeout=10.0) is True
    assert not eng._worker.is_alive()


def test_reset_engine_closes_previous_singleton(tmp_path, monkeypatch):
    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    from sutro_tpu.engine import api as api_mod

    eng = api_mod.get_engine()
    worker = eng._worker
    api_mod.reset_engine()
    worker.join(timeout=10.0)
    assert not worker.is_alive()


# ------------------------------------------------- resource lifecycle


def test_resource_leak_on_early_return():
    active, _ = scan(
        """
import socket

def f(flag):
    s = socket.create_connection(("h", 1))
    if flag:
        return None
    s.sendall(b"x")
    s.close()
    return s
"""
    )
    (f,) = [f for f in active if f.rule == "resource-leak"]
    assert f.key == "socket:s" and "early return" in f.message


def test_resource_leak_on_exception_edge():
    # the function owns kv-pages (it frees them on the happy path), so
    # a call that can raise between alloc and free leaks the pages
    active, _ = scan(
        """
def f(alloc, work):
    pages = alloc.alloc(4)
    work(1)
    alloc.free(pages)
"""
    )
    (f,) = [f for f in active if f.rule == "resource-leak"]
    assert f.key == "kv-pages:pages" and "exception path" in f.message


def test_resource_release_in_handler_is_clean():
    active, _ = scan(
        """
def g(alloc, work):
    pages = alloc.alloc(4)
    try:
        work(1)
    except Exception:
        alloc.free(pages)
        raise
    alloc.free(pages)
"""
    )
    assert "resource-leak" not in rules_of(active)


def test_resource_daemon_thread_untracked():
    active, _ = scan(
        """
import threading

def h(fn):
    t = threading.Thread(target=fn, daemon=True)
    t.start()
"""
    )
    assert "resource-leak" not in rules_of(active)


def test_resource_none_branch_refined_away():
    # `if h is None: return` is a miss, not a leak
    active, _ = scan(
        """
def f(store):
    h = store.lookup_pin("k")
    if h is None:
        return 0
    store.release(h)
    return 1
"""
    )
    assert "resource-leak" not in rules_of(active)


def test_resource_return_escape_transfers_ownership():
    active, _ = scan(
        """
from serving.channel import StreamChannel

def mk():
    ch = StreamChannel()
    return ch
"""
    )
    assert "resource-leak" not in rules_of(active)


def test_resource_leak_pragma_suppressed():
    active, suppressed = scan(
        """
import socket

def f(flag):
    s = socket.create_connection(("h", 1))  # graftlint: disable=resource-leak
    if flag:
        return None
    s.close()
    return None
"""
    )
    assert "resource-leak" not in rules_of(active)
    assert "resource-leak" in rules_of(suppressed)


def test_resource_double_release_flagged():
    active, _ = scan(
        """
def f(alloc):
    pages = alloc.alloc(2)
    alloc.free(pages)
    alloc.free(pages)
"""
    )
    (f,) = [f for f in active if f.rule == "resource-double-release"]
    assert f.key == "kv-pages:pages"


def test_resource_release_on_each_branch_is_clean():
    active, _ = scan(
        """
def f(alloc, ok):
    pages = alloc.alloc(2)
    if ok:
        alloc.free(pages)
    else:
        alloc.free(pages)
"""
    )
    assert "resource-double-release" not in rules_of(active)
    assert "resource-leak" not in rules_of(active)


# ------------------------------------------------- trace context


def test_trace_ctx_dropped_on_early_return():
    active, _ = scan(
        """
def f(store, flag):
    tr = store.start_trace("tr-1", "interactive")
    if flag:
        return None
    tr.end("ok")
    return None
"""
    )
    (f,) = [f for f in active if f.rule == "trace-ctx-dropped"]
    assert f.key == "trace-ctx:tr" and "early return" in f.message


def test_trace_ctx_ended_by_id_or_method_is_clean():
    active, _ = scan(
        """
def f(store, flag):
    tr = store.start_trace("tr-1")
    if flag:
        store.end_trace(tr)
        return 1
    tr.end("err")
    return 0
"""
    )
    assert "trace-ctx-dropped" not in rules_of(active)


def test_trace_ctx_bare_start_is_cross_function_handoff():
    # the gateway pattern: no handle bound, the id string IS the
    # propagated context — finish() ends it elsewhere
    active, _ = scan(
        """
def submit(store, rid):
    store.start_trace(f"tr-{rid}", "interactive")
    return rid
"""
    )
    assert "trace-ctx-dropped" not in rules_of(active)


def test_trace_ctx_return_escape_transfers_ownership():
    active, _ = scan(
        """
def start(store):
    tr = store.start_trace("tr-1")
    return tr
"""
    )
    assert "trace-ctx-dropped" not in rules_of(active)


def test_trace_ctx_pragma_suppressed():
    active, suppressed = scan(
        """
def f(store, flag):
    tr = store.start_trace("tr-1")  # graftlint: disable=trace-ctx-dropped
    if flag:
        return None
    tr.end("ok")
    return None
"""
    )
    assert "trace-ctx-dropped" not in rules_of(active)
    assert "trace-ctx-dropped" in rules_of(suppressed)


# ------------------------------------------------- wire protocol


def _wire_idx(src: str) -> PackageIndex:
    idx = PackageIndex()
    idx.add_source("dphost.py", src, "dphost")
    return idx


def test_wire_key_removed_vs_schema():
    from sutro_tpu.analysis import protocol

    idx = _wire_idx(
        """
def _send(sock, m):
    pass

def send_res(sock):
    _send(sock, {"t": "res", "rows": 1})
"""
    )
    schema = {
        "version": 1,
        "frames": {"res": ["t", "rows", "gone"], "hb": ["t"]},
    }
    fs = protocol.run(idx, schema=schema)
    assert sorted(f.key for f in fs if f.rule == "wire-key-removed") == [
        "hb",  # whole frame vanished
        "res.gone",  # one key vanished
    ]


def test_wire_added_keys_are_fine():
    from sutro_tpu.analysis import protocol

    idx = _wire_idx(
        """
def _send(sock, m):
    pass

def send_res(sock):
    m = {"t": "res", "rows": 1}
    m["extra"] = 2
    _send(sock, m)

def parse(m):
    return m.get("rows", 0)
"""
    )
    schema = {"version": 1, "frames": {"res": ["t", "rows"]}}
    assert protocol.run(idx, schema=schema) == []


def test_wire_strict_parse_flagged():
    from sutro_tpu.analysis import protocol

    idx = _wire_idx(
        """
def _send(sock, m):
    pass

def parse(m):
    if set(m) == {"t", "rows"}:
        pass
    for k in m:
        if k not in ("t", "rows"):
            raise ValueError(k)
"""
    )
    fs = protocol.run(idx, schema={"version": 1, "frames": {}})
    assert sorted(f.key for f in fs if f.rule == "wire-strict-parse") == [
        "shape-eq",
        "unknown-key-raise",
    ]


def test_wire_pass_ignores_non_wire_modules():
    # frame-shaped dicts in ordinary modules aren't wire frames
    active, _ = scan(
        """
def build():
    return {"t": "res", "rows": 1}

def parse(m):
    if set(m) == {"t"}:
        raise ValueError(m)
"""
    )
    assert "wire-strict-parse" not in rules_of(active)
    assert "wire-key-removed" not in rules_of(active)


# ------------------------------------------------- kill-switch zero-op


def test_killswitch_bare_metric_write_flagged():
    active, _ = scan(
        """
import os
import telemetry

ENABLED = os.environ.get("SUTRO_TELEMETRY", "1") not in ("0",)

def hot():
    telemetry.ROWS_TOTAL.inc(1.0, "ok")
"""
    )
    (f,) = [f for f in active if f.rule == "killswitch-ungated"]
    assert f.key == "telemetry:ROWS_TOTAL.inc"


def test_killswitch_gate_and_guard_clause_clean():
    active, _ = scan(
        """
import os
import telemetry

ENABLED = os.environ.get("SUTRO_TELEMETRY", "1") not in ("0",)

def gated():
    if ENABLED:
        telemetry.ROWS_TOTAL.inc(1.0, "ok")

def guarded():
    if not ENABLED:
        return
    telemetry.ROWS_TOTAL.inc(1.0, "ok")
"""
    )
    assert "killswitch-ungated" not in rules_of(active)


def test_killswitch_internally_gated_callee_clean():
    # stage_observe checks the flag itself; callers stay bare
    idx = PackageIndex()
    idx.add_source(
        "telemetry/__init__.py",
        """
import os

ENABLED = os.environ.get("SUTRO_TELEMETRY", "1") not in ("0",)

def stage_observe(stage, dur):
    if not ENABLED:
        return
    STAGE.observe(dur, stage)
""",
        "telemetry",
    )
    idx.add_source(
        "m.py",
        """
import telemetry

def hot():
    telemetry.stage_observe("decode", 0.1)
""",
        "m",
    )
    active, _ = core.apply_suppressions(idx, run_passes(idx))
    assert "killswitch-ungated" not in rules_of(active)


def test_killswitch_pragma_suppressed():
    active, suppressed = scan(
        """
import os
import telemetry

ENABLED = os.environ.get("SUTRO_TELEMETRY", "1") not in ("0",)

def hot():
    telemetry.ROWS_TOTAL.inc(1.0, "ok")  # graftlint: disable=killswitch-ungated
"""
    )
    assert "killswitch-ungated" not in rules_of(active)
    assert "killswitch-ungated" in rules_of(suppressed)


# ------------------------------------------------- telemetry cardinality


def test_cardinality_uncapped_and_identifier_labels():
    active, _ = scan(
        """
C_UNCAPPED = REGISTRY.counter("m_total", "h", labels=("stage",))
C_CAPPED = REGISTRY.counter("n_total", "h", labels=("stage",), max_series=8)

def f(stage, job_id):
    C_UNCAPPED.inc(1.0, stage)
    C_CAPPED.inc(1.0, job_id)
    C_CAPPED.inc(1.0, f"job-{job_id}")
"""
    )
    keys = sorted(
        f.key for f in active if f.rule == "telemetry-cardinality"
    )
    assert keys == [
        "m_total:uncapped",  # non-const label, no max_series budget
        "n_total:identifier",  # job_id name
        "n_total:identifier",  # f-string
    ]


def test_cardinality_capped_nonconst_and_const_labels_clean():
    active, _ = scan(
        """
C_CAPPED = REGISTRY.counter("n_total", "h", labels=("stage",), max_series=8)

def f(stage):
    C_CAPPED.inc(1.0, stage)
    C_CAPPED.inc(1.0, "const")
"""
    )
    assert "telemetry-cardinality" not in rules_of(active)


# ------------------------------------------------- stale suppressions


def scan_with_stale(src: str):
    idx = PackageIndex()
    idx.add_source("m.py", src, "m")
    active, suppressed = core.apply_suppressions(idx, run_passes(idx))
    active.extend(core.stale_suppression_findings(idx, suppressed))
    return active, suppressed


def test_stale_suppression_flagged():
    active, _ = scan_with_stale(
        """
x = 1  # graftlint: disable=lock-order
"""
    )
    (f,) = [f for f in active if f.rule == "stale-suppression"]
    assert "lock-order" in f.message


def test_masking_suppression_is_not_stale():
    active, suppressed = scan_with_stale(
        """
import socket

def f(flag):
    s = socket.create_connection(("h", 1))  # graftlint: disable=resource-leak
    if flag:
        return None
    s.close()
    return None
"""
    )
    assert active == []
    assert len(suppressed) == 1


# --------------------------------------- injection gates: new passes


def test_injected_wire_key_removal_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    dp = dst / "engine" / "dphost.py"
    src = dp.read_text()
    anchor = '{"t": "reshard", "rows": sorted(rows)}'
    assert anchor in src
    dp.write_text(src.replace(anchor, '{"t": "reshard"}', 1))
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "wire-key-removed" in res.stdout
    assert "reshard" in res.stdout


def test_injected_dropped_release_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    sched = dst / "engine" / "scheduler.py"
    src = sched.read_text()
    anchor = "store.release(handle)"
    assert anchor in src
    sched.write_text(src.replace(anchor, 'logger.debug("skip")', 1))
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "resource-leak" in res.stdout


def test_injected_ungated_metric_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    js = dst / "engine" / "jobstore.py"
    js.write_text(
        js.read_text()
        + """

def _injected_hot(n):
    telemetry.ROWS_TOTAL.inc(float(n), "injected")
"""
    )
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "killswitch-ungated" in res.stdout


def test_injected_dropped_trace_handle_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    gw = dst / "serving" / "gateway.py"
    gw.write_text(
        gw.read_text()
        + """

def _injected_trace(flag):
    tr = telemetry.TRACES.start_trace("tr-injected")
    if flag:
        return None
    tr.end("ok")
    return None
"""
    )
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "trace-ctx-dropped" in res.stdout


def test_injected_unforwarded_fleet_trace_fails_gate(tmp_path):
    """The fleet sub-pass of trace-ctx-dropped: strip the router's
    ``trace_id=tid`` forwarding from its upstream relay — the request
    still works, but the replica half of every cross-process stitch is
    silently lost, and the gate must catch exactly that."""
    dst = _copy_tree(tmp_path)
    rt = dst / "fleet" / "router.py"
    src = rt.read_text()
    anchor = "                    trace_id=tid,\n"
    assert anchor in src
    rt.write_text(src.replace(anchor, "", 1))
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "trace-ctx-dropped" in res.stdout
    assert "fleet/router.py" in res.stdout
    assert "never forwarded" in res.stdout


def test_injected_identifier_label_fails_gate(tmp_path):
    dst = _copy_tree(tmp_path)
    js = dst / "engine" / "jobstore.py"
    js.write_text(
        js.read_text()
        + """

def _injected_label(job_id):
    if telemetry.ENABLED:
        telemetry.ROWS_TOTAL.inc(1.0, f"job-{job_id}")
"""
    )
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "telemetry-cardinality" in res.stdout
    assert "killswitch-ungated" not in res.stdout  # the gate is honored


# --------------------------------------------------------- diff mode


def test_diff_mode_scopes_findings_to_changed_lines(tmp_path):
    dst = _copy_tree(tmp_path)

    def git(*a):
        subprocess.run(
            ["git", "-c", "user.email=t@t.t", "-c", "user.name=t", *a],
            cwd=tmp_path,
            check=True,
            capture_output=True,
        )

    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # clean tree: baselined findings exist, but no changed lines
    res = run_cli(["sutro_tpu", "--diff", "HEAD"], cwd=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 finding(s) on lines changed" in res.stdout
    # a violation on a changed line is reported without a baseline
    sched = dst / "engine" / "scheduler.py"
    src = sched.read_text()
    anchor = "self._prep_pump(order)"
    src = src.replace(
        anchor, anchor + "\n                _wall = time.time()", 1
    )
    sched.write_text(src)
    res = run_cli(["sutro_tpu", "--diff", "HEAD"], cwd=tmp_path)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "sched-nondeterminism" in res.stdout
    assert "finding(s) on lines changed vs HEAD" in res.stdout


# ----------------------------- engine fixes the new passes drove


def test_openai_collect_cancels_channel_on_decoder_error():
    from types import SimpleNamespace

    from sutro_tpu.serving import openai as oai
    from sutro_tpu.serving.channel import StreamChannel

    ch = StreamChannel()
    ch.put_token(0, 1, 0.0)

    def bad_decoder():
        def d(tok):
            raise ValueError("decoder boom")

        return d

    ir = SimpleNamespace(
        channel=ch,
        decoder=bad_decoder,
        prompt_tokens=1,
        id="req-1",
        created_unix=0,
        model="m",
    )
    with pytest.raises(ValueError, match="decoder boom"):
        oai.collect(ir, chat=False, timeout=5.0)
    # the producer side must stop too: without cancel() the scheduler
    # keeps generating tokens for a stream nobody reads
    assert ch.cancelled


def test_prefix_store_counters_gated_on_kill_switch():
    import numpy as np

    from sutro_tpu import telemetry
    from sutro_tpu.engine.prefixstore import PrefixStore

    def misses():
        return (
            telemetry.REGISTRY.collect()
            .get("sutro_prefix_store_misses_total", {})
            .get("series", {})
            .get("", 0.0)
        )

    prev = telemetry.ENABLED
    try:
        telemetry.set_enabled(False)
        s = PrefixStore(8)
        before = misses()
        h = s.lookup_pin(np.arange(32, dtype=np.int32))
        s.release(h)
        assert misses() == before  # switch off means zero work
        telemetry.set_enabled(True)
        h = s.lookup_pin(np.arange(64, dtype=np.int32) + 1000)
        s.release(h)
        assert misses() == before + 1
    finally:
        telemetry.set_enabled(prev)


def test_stage_observe_is_zero_op_when_disabled():
    from sutro_tpu import telemetry

    prev = telemetry.ENABLED
    try:
        telemetry.set_enabled(False)
        before = (
            telemetry.REGISTRY.collect()
            .get("sutro_stage_seconds", {})
            .get("series", {})
        )
        telemetry.stage_observe("zz_probe_disabled", 1.0)
        after = (
            telemetry.REGISTRY.collect()
            .get("sutro_stage_seconds", {})
            .get("series", {})
        )
        assert before == after
    finally:
        telemetry.set_enabled(prev)


def test_preemption_priority_labels_bounded():
    from sutro_tpu.engine.control import _prio_label

    assert _prio_label(3) == "3"
    assert _prio_label(-1) == "-1"
    assert _prio_label(0) == "0"
    # out-of-ladder priorities collapse instead of minting new series
    assert _prio_label(999) == "other"
    assert _prio_label(-7) == "other"


def test_failure_log_label_collapses_nonstring_kind(tmp_path):
    from sutro_tpu import telemetry
    from sutro_tpu.engine.jobstore import JobStore

    prev = telemetry.ENABLED
    try:
        telemetry.set_enabled(True)
        store = JobStore(root=tmp_path)
        rec = store.create(model="m", num_rows=1)
        store.append_failure_log(rec.job_id, {"event": 123})
        series = telemetry.REGISTRY.collect()[
            "sutro_failure_events_total"
        ]["series"]
        assert "123" not in series
        assert series.get("unknown", 0) >= 1
    finally:
        telemetry.set_enabled(prev)


# ------------------------------------------ data races / atomicity (v3)

RACE_RULES = {
    "shared-state-unlocked",
    "lockset-inconsistent",
    "check-then-act",
}


def race_findings(findings):
    return [f for f in findings if f.rule in RACE_RULES]


def test_shared_state_unlocked_flagged():
    active, _ = scan(
        """
import threading

class C:
    def __init__(self):
        self.n = 0
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        self.n += 1

    def read(self):
        return self.n
"""
    )
    hits = race_findings(active)
    assert [f.rule for f in hits] == ["shared-state-unlocked"]
    assert "C.n" in hits[0].message


def test_shared_state_common_lock_clean():
    active, _ = scan(
        """
import threading

class C:
    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        with self._lock:
            self.n += 1

    def read(self):
        with self._lock:
            return self.n
"""
    )
    assert race_findings(active) == []


def test_lockset_inconsistent_disjoint_locks():
    active, _ = scan(
        """
import threading

class C:
    def __init__(self):
        self.n = 0
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        with self._a:
            self.n += 1

    def read(self):
        with self._b:
            return self.n
"""
    )
    assert [f.rule for f in race_findings(active)] == [
        "lockset-inconsistent"
    ]


def test_join_orders_spawner_accesses():
    active, _ = scan(
        """
import threading

class C:
    def run(self):
        t = threading.Thread(target=self._work)
        t.start()
        t.join()
        return self.n

    def _work(self):
        self.n += 1
"""
    )
    assert race_findings(active) == []


def test_queue_handoff_counts_as_happens_before():
    active, _ = scan(
        """
import queue
import threading

class C:
    def __init__(self):
        self.q = queue.Queue()
        self.latest = None
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        while True:
            item = self.q.get()
            self.latest = item

    def peek(self):
        self.q.put(1)
        return self.latest
"""
    )
    assert race_findings(active) == []


def test_publication_before_start_exempt_after_start_flagged():
    """Writes in the spawner BEFORE .start() are publication (clean);
    the same write moved after the start races the fresh thread."""
    before = """
import threading

class C:
    def __init__(self):
        self.cfg = {}
        self._t = threading.Thread(target=self._work, daemon=True)
        self.cfg = {"ready": True}
        self._t.start()

    def _work(self):
        if self.cfg:
            pass
"""
    active, _ = scan(before)
    assert race_findings(active) == []
    after = before.replace(
        '        self.cfg = {"ready": True}\n        self._t.start()',
        '        self._t.start()\n        self.cfg = {"ready": True}',
    )
    assert after != before
    active, _ = scan(after)
    assert [f.rule for f in race_findings(active)] == [
        "shared-state-unlocked"
    ]


def test_shared_state_suppressed():
    active, suppressed = scan(
        """
import threading

class C:
    def __init__(self):
        self.n = 0
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        self.n += 1  # graftlint: disable=shared-state-unlocked

    def read(self):
        return self.n
"""
    )
    assert race_findings(active) == []
    assert [f.rule for f in race_findings(suppressed)] == [
        "shared-state-unlocked"
    ]


def test_check_then_act_split_rmw_flagged():
    active, _ = scan(
        """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0

    def bump(self):
        with self._lock:
            cur = self.count
        with self._lock:
            self.count = cur + 1
"""
    )
    hits = race_findings(active)
    assert [f.rule for f in hits] == ["check-then-act"]
    assert "C.count" in hits[0].message


def test_check_then_act_single_block_and_rebind_clean():
    active, _ = scan(
        """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.cache = None

    def bump(self):
        with self._lock:
            self.count += 1

    def rebuild(self):
        # double-checked publish: `tok` is rebuilt from scratch between
        # the two critical sections, so no stale read flows into the
        # second write
        with self._lock:
            tok = self.cache
        if tok is None:
            tok = object()
        with self._lock:
            self.cache = tok
"""
    )
    assert race_findings(active) == []


def test_threads_inventory_cli():
    res = run_cli(["sutro_tpu", "--threads"], cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    out = res.stdout
    assert "Monitor._loop" in out
    assert "LocalEngine._worker_loop" in out
    assert "KVTierPool._run_worker" in out
    # one line per root, not per spawn re-visit (dedupe regression)
    assert out.count("KVTierPool._run_worker") == 1
    assert "thread root(s)" in out


def test_sarif_report_shape():
    res = run_cli(
        ["sutro_tpu", "--no-baseline", "--format", "sarif"], cwd=REPO
    )
    assert res.returncode == 1  # findings exist without a baseline
    doc = json.loads(res.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "graftlint"
    rule_ids = {r["id"] for r in driver["rules"]}
    assert run["results"]
    for r in run["results"]:
        assert r["ruleId"] in rule_ids
        assert r["partialFingerprints"]["graftlint/v1"]
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"]
        assert loc["region"]["startLine"] >= 1


def test_injected_unlocked_write_fails_gate(tmp_path):
    """Deleting a real lock acquisition (set_rules' guard on the rule
    tables) must trip shared-state-unlocked against the baseline."""
    dst = _copy_tree(tmp_path)
    mon = dst / "telemetry" / "monitor.py"
    src = mon.read_text()
    old = (
        "        with self._lock:\n"
        "            self._rules = list(rules)\n"
        "            self._rule_state = "
        "{r.name: _RuleState() for r in self._rules}"
    )
    assert old in src
    new = (
        "        self._rules = list(rules)\n"
        "        self._rule_state = "
        "{r.name: _RuleState() for r in self._rules}"
    )
    mon.write_text(src.replace(old, new, 1))
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "shared-state-unlocked" in res.stdout


def test_injected_split_rmw_fails_gate(tmp_path):
    """Splitting a guarded RMW (the prep-overlap counter) across two
    critical sections must trip check-then-act against the baseline."""
    dst = _copy_tree(tmp_path)
    sched = dst / "engine" / "scheduler.py"
    src = sched.read_text()
    old = (
        "            with self._prep_lock:\n"
        "                self.prep_overlap_s += dt"
    )
    assert old in src
    new = (
        "            with self._prep_lock:\n"
        "                _cur = self.prep_overlap_s\n"
        "            with self._prep_lock:\n"
        "                self.prep_overlap_s = _cur + dt"
    )
    sched.write_text(src.replace(old, new, 1))
    res = run_cli(
        ["sutro_tpu", "--baseline", str(BASELINE)], cwd=tmp_path
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "check-then-act" in res.stdout


def test_lint_wall_time_within_tier1_budget():
    """The whole-tree scan must fit the 60s tier-1 budget the Makefile
    enforces (timeout would hard-fail CI; this catches creep early)."""
    t0 = time.perf_counter()
    core.analyze([str(REPO / "sutro_tpu")])
    assert time.perf_counter() - t0 < 60.0
