"""The engine's constraint-factory table (engine/constrain/fsm.py
``FactoryTable``): one ``ConstraintFactory`` per (schema, tokenizer),
built at most once and kept; the submit probe, the session, the
gateway's constrained chat and the stage graph all ask it.

CPU, the tiny preset, ``ByteTokenizer``. Every schema here is this
file's own, so what the shared ``live_engine`` served before does not
count as a hit.
"""

import json
import sys
import threading
import time

import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.constrain import fsm
from sutro_tpu.engine.constrain.fsm import FactoryTable
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.interfaces import JobStatus
from sutro_tpu.models.configs import MODEL_CONFIGS
from tests.test_stagegraph import _submit as _submit_job
from tests.test_stagegraph import _wait_terminal

RESULTS = ("hit", "miss", "wait")


def _schema(tag: str, max_length: int = 12) -> dict:
    """An object schema no other test uses (``tag`` is in an enum)."""
    return {
        "type": "object",
        "properties": {
            "note": {"type": "string", "maxLength": max_length},
            "label": {"enum": [f"{tag}-yes", f"{tag}-no"]},
        },
        "required": ["note", "label"],
    }


def _counts() -> dict:
    series = (
        telemetry.REGISTRY.collect().get("sutro_constraint_factory_total")
        or {}
    ).get("series", {})
    return {k: float(series.get(k, 0.0)) for k in RESULTS}


def _delta(before: dict) -> dict:
    after = _counts()
    return {k: after[k] - before[k] for k in RESULTS}


@pytest.fixture()
def counting_builder(monkeypatch):
    """The from-scratch builder behind a wrapper that counts its calls."""
    calls = []
    real = fsm.schema_constraint_factory

    def build(schema, tokenizer):
        calls.append(json.dumps(schema, sort_keys=True))
        return real(schema, tokenizer)

    monkeypatch.setattr(fsm, "schema_constraint_factory", build)
    return calls


def _submit(eng, inputs, **kw):
    return _submit_job(eng, inputs, max_new=48, **kw)


def _rows(eng, job_id):
    df = eng.jobs.read_results(job_id).sort_values("row_id")
    return df["outputs"].tolist(), df["finish_reason"].tolist()


# ---------------------------------------------------------------------------
# (a) one owner: the four sites get the same object from one build
# ---------------------------------------------------------------------------


def test_four_sites_share_one_factory(live_engine, counting_builder, monkeypatch):
    eng, _url, _home = live_engine
    schema = _schema("sites")
    handed = []
    real_get = eng.constraint_factories.factory_for

    def get(sch, tok):
        fac, how = real_get(sch, tok)
        if sch == schema:
            handed.append((fac, how))
        return fac, how

    monkeypatch.setattr(eng.constraint_factories, "factory_for", get)

    # the submit probe and the session
    jid = _submit(eng, ["a review", "another"], output_schema=schema)
    assert _wait_terminal(eng, jid) == JobStatus.SUCCEEDED
    assert len(handed) == 2
    # the gateway's constrained chat
    from sutro_tpu.serving.openai import collect, parse_request

    body = {
        "model": "tiny-dense", "temperature": 0.0, "max_tokens": 48,
        "messages": [{"role": "user", "content": "classify this"}],
        "response_format": {
            "type": "json_schema",
            "json_schema": {"name": "out", "schema": schema},
        },
    }
    out = collect(
        eng.gateway.submit(parse_request(body, chat=True)), chat=True,
        timeout=180,
    )
    assert json.loads(out["choices"][0]["message"]["content"])["label"] in (
        "sites-yes", "sites-no",
    )
    assert len(handed) == 3
    # a stage with a schema: the stage's session and the stage graph
    gid = _submit(
        eng, ["x", "y"],
        stages=[{"name": "cls", "kind": "map", "output_schema": schema,
                 "sampling_params": {"max_new_tokens": 48}}],
    )
    assert _wait_terminal(eng, gid) == JobStatus.SUCCEEDED
    assert len(handed) == 5

    assert counting_builder.count(json.dumps(schema, sort_keys=True)) == 1
    assert [how for _f, how in handed] == ["miss"] + ["hit"] * 4
    assert len({id(f) for f, _how in handed}) == 1


# ---------------------------------------------------------------------------
# (b) the key: canonical schema text and the tokenizer instance
# ---------------------------------------------------------------------------


def _reordered(schema: dict) -> dict:
    return {
        "required": list(schema["required"]),
        "properties": {
            "label": dict(reversed(list(schema["properties"]["label"].items()))),
            "note": {"maxLength": schema["properties"]["note"]["maxLength"],
                     "type": "string"},
        },
        "type": "object",
    }


@pytest.mark.parametrize(
    "case,expect",
    [
        ("same_dict_again", "hit"),
        ("keys_in_another_order", "hit"),
        ("json_round_trip", "hit"),
        ("another_max_length", "miss"),
        ("another_tokenizer_instance", "miss"),
    ],
)
def test_key_is_canonical_schema_and_tokenizer(
    case, expect, byte_tok, counting_builder
):
    table = FactoryTable()
    base = _schema("key")
    first, how = table.factory_for(base, byte_tok)
    assert how == "miss"
    schema, tok = base, byte_tok
    if case == "keys_in_another_order":
        schema = _reordered(base)
        assert list(schema) != list(base) and schema == base
    elif case == "json_round_trip":
        schema = json.loads(json.dumps(base))
    elif case == "another_max_length":
        schema = _schema("key", max_length=13)
    elif case == "another_tokenizer_instance":
        tok = ByteTokenizer(vocab_size=MODEL_CONFIGS["tiny-dense"].vocab_size)
    again, how = table.factory_for(schema, tok)
    assert how == expect
    assert (again is first) == (expect == "hit")
    assert len(counting_builder) == (1 if expect == "hit" else 2)
    assert len(table) == (1 if expect == "hit" else 2)


def test_token_table_hangs_off_the_tokenizer(byte_tok):
    """Two schemas on one tokenizer read one ``TokenTable``; another
    tokenizer instance has its own."""
    table = FactoryTable()
    a, _ = table.factory_for(_schema("tt-a"), byte_tok)
    b, _ = table.factory_for(_schema("tt-b"), byte_tok)
    other = ByteTokenizer(vocab_size=MODEL_CONFIGS["tiny-dense"].vocab_size)
    c, _ = table.factory_for(_schema("tt-a"), other)
    assert a.table is b.table is fsm.token_table(byte_tok)
    assert c.table is not a.table
    assert a.masks is not b.masks and a.nfa is not b.nfa


# ---------------------------------------------------------------------------
# (c) single flight; a build that raises is not cached
# ---------------------------------------------------------------------------


def test_single_flight_one_build_one_miss_one_wait(byte_tok, monkeypatch):
    table = FactoryTable()
    started, release = threading.Event(), threading.Event()
    builds = []
    real = fsm.schema_constraint_factory

    def slow(schema, tokenizer):
        builds.append(1)
        started.set()
        assert release.wait(30)
        return real(schema, tokenizer)

    monkeypatch.setattr(fsm, "schema_constraint_factory", slow)
    schema, got = _schema("flight"), []
    before = _counts()

    def ask():
        got.append(table.factory_for(schema, byte_tok))

    leader = threading.Thread(target=ask)
    leader.start()
    assert started.wait(30)
    follower = threading.Thread(target=ask)
    follower.start()
    time.sleep(0.2)  # the follower is inside factory_for(), waiting
    assert follower.is_alive() and len(builds) == 1
    release.set()
    leader.join(30)
    follower.join(30)
    assert not leader.is_alive() and not follower.is_alive()
    assert len(builds) == 1
    assert sorted(how for _f, how in got) == ["miss", "wait"]
    assert got[0][0] is got[1][0]
    assert _delta(before) == {"hit": 0.0, "miss": 1.0, "wait": 1.0}


def test_different_keys_build_at_once(byte_tok, monkeypatch):
    """A build in flight does not hold up another key's build."""
    table = FactoryTable()
    release = threading.Event()
    real = fsm.schema_constraint_factory
    slow_text = json.dumps(_schema("held"), sort_keys=True)

    def build(schema, tokenizer):
        if json.dumps(schema, sort_keys=True) == slow_text:
            assert release.wait(30)
        return real(schema, tokenizer)

    monkeypatch.setattr(fsm, "schema_constraint_factory", build)
    held = threading.Thread(
        target=table.factory_for, args=(_schema("held"), byte_tok)
    )
    held.start()
    try:
        t0 = time.monotonic()
        _fac, how = table.factory_for(_schema("free"), byte_tok)
        assert how == "miss" and time.monotonic() - t0 < 10
        assert held.is_alive()
    finally:
        release.set()
        held.join(30)
    assert not held.is_alive() and len(table) == 2


@pytest.mark.parametrize("waiter", [False, True])
def test_failed_build_is_not_cached(byte_tok, monkeypatch, waiter):
    """A bad schema fails every time with its own error; a thread that
    waited for a build that raised builds for itself."""
    table = FactoryTable()
    started, release = threading.Event(), threading.Event()
    builds = []

    def bad(schema, tokenizer):
        builds.append(1)
        started.set()
        if waiter:
            assert release.wait(30)
        raise ValueError(f"bad schema, build {len(builds)}")

    monkeypatch.setattr(fsm, "schema_constraint_factory", bad)
    schema, errors = _schema("bad"), []

    def ask():
        try:
            table.factory_for(schema, byte_tok)
        except ValueError as e:
            errors.append(str(e))

    if waiter:
        a = threading.Thread(target=ask)
        a.start()
        assert started.wait(30)
        b = threading.Thread(target=ask)
        b.start()
        time.sleep(0.2)
        release.set()
        a.join(30)
        b.join(30)
        assert not a.is_alive() and not b.is_alive()
    else:
        ask()
        ask()
    assert sorted(errors) == ["bad schema, build 1", "bad schema, build 2"]
    assert len(table) == 0 and not table._building


def test_stress_many_threads_few_keys(byte_tok, counting_builder):
    """More threads than cores, a short switch interval: every key is
    built exactly once and every ask of a key gets the same object."""
    table = FactoryTable()
    schemas = [_schema(f"stress-{i}") for i in range(FactoryTable.MAX_ENTRIES)]
    got = [[] for _ in schemas]
    n_threads, rounds = 24, 20
    go = threading.Barrier(n_threads)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def worker(w):
        go.wait(30)
        for r in range(rounds):
            i = (w + r) % len(schemas)
            fac, _how = table.factory_for(dict(schemas[i]), byte_tok)
            got[i].append(fac)

    try:
        threads = [
            threading.Thread(target=worker, args=(w,)) for w in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(counting_builder) == len(schemas)
    assert sum(len(g) for g in got) == n_threads * rounds
    for g in got:
        assert len({id(f) for f in g}) == 1
    assert not table._building


# ---------------------------------------------------------------------------
# (d) the same rows cold and warm; two jobs on one factory at once
# ---------------------------------------------------------------------------

_REVIEWS = [
    "great phone, battery lasts", "arrived broken", "it is a phone",
    "would buy again", "never again", "fine for the price",
]


def _wait_engine_idle(eng, timeout=120.0):
    """The shared ``live_engine`` may still be ending a job of the test
    file that ran before this one in the same worker: its rows would
    share the cold job's batch and not the warm one's, and its session's
    lookup would land in the counter between two reads of it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(JobStatus(j["status"]).is_terminal() for j in eng.list_jobs()):
            return
        time.sleep(0.05)
    raise TimeoutError("the shared engine never went idle")


def test_rows_bit_identical_cold_and_warm(
    live_engine, counting_builder, monkeypatch
):
    eng, _url, _home = live_engine
    schema = _schema("coldwarm")
    _wait_engine_idle(eng)
    # THIS schema's lookups, as ``test_four_sites_share_one_factory``
    # counts them: the registry's counter is the whole process's, and
    # whatever else the shared engine serves meanwhile (another file's
    # job ending, a chat) counts there too
    asked = []
    real_get = eng.constraint_factories.factory_for

    def get(sch, tok):
        fac, how = real_get(sch, tok)
        if sch == schema:
            asked.append(how)
        return fac, how

    monkeypatch.setattr(eng.constraint_factories, "factory_for", get)
    before = _counts()
    cold = _submit(eng, _REVIEWS, output_schema=schema)
    assert _wait_terminal(eng, cold) == JobStatus.SUCCEEDED
    assert asked == ["miss", "hit"]         # the submit probe, the session
    warm = _submit(eng, _REVIEWS, output_schema=schema)
    assert _wait_terminal(eng, warm) == JobStatus.SUCCEEDED
    assert asked == ["miss", "hit", "hit", "hit"]
    delta = _delta(before)
    assert delta["miss"] >= 1.0 and delta["hit"] >= 3.0
    assert len(counting_builder) == 1
    out_cold, why_cold = _rows(eng, cold)
    out_warm, why_warm = _rows(eng, warm)
    assert out_cold == out_warm and why_cold == why_warm
    assert set(why_cold) == {"schema_complete"}
    # and equal to what a factory nobody shared gives
    eng.constraint_factories = FactoryTable()
    fresh = _submit(eng, _REVIEWS, output_schema=schema)
    assert _wait_terminal(eng, fresh) == JobStatus.SUCCEEDED
    assert _rows(eng, fresh) == (out_cold, why_cold)
    assert len(counting_builder) == 2


def test_two_jobs_at_once_on_one_factory(live_engine, counting_builder):
    """Two jobs decode together (the second attaches to the first's
    session) on ONE factory: no FSM state leaks between rows."""
    eng, _url, _home = live_engine
    schema = _schema("together", max_length=8)
    jobs = [
        _submit(eng, [f"{r} ({j})" for r in _REVIEWS], output_schema=schema)
        for j in range(2)
    ]
    for jid in jobs:
        assert _wait_terminal(eng, jid) == JobStatus.SUCCEEDED
    assert len(counting_builder) == 1
    for jid in jobs:
        outputs, reasons = _rows(eng, jid)
        assert set(reasons) == {"schema_complete"}
        for text in outputs:
            value = json.loads(text)
            assert set(value) == {"note", "label"}
            assert len(value["note"]) <= 8
            assert value["label"] in ("together-yes", "together-no")


def test_cached_masks_are_read_only(byte_tok):
    """What the table shares cannot be written through: a caller that
    wants to change a mask copies it."""
    fac, _ = FactoryTable().factory_for(_schema("readonly"), byte_tok)
    row = fac()
    mask = row.allowed_tokens()
    m, dist = fac.masks.mask_and_dist(row.states)
    assert mask is m and not m.flags.writeable and not dist.flags.writeable
    with pytest.raises(ValueError):
        mask[0] = True
    # the budget-filtered mask is the caller's own
    assert row.allowed_tokens(remaining=64).flags.writeable
    # a second row starts from the initial state whatever the first did
    row.advance(int(mask.nonzero()[0][0]))
    assert fac().states == fac.nfa.initial() != row.states


# ---------------------------------------------------------------------------
# (e) the bound
# ---------------------------------------------------------------------------


def test_bound_evicts_least_recently_used(byte_tok, counting_builder):
    table = FactoryTable()
    n = FactoryTable.MAX_ENTRIES
    schemas = [_schema(f"lru-{i}") for i in range(n + 1)]
    first = [table.factory_for(s, byte_tok)[0] for s in schemas[:n]]
    assert len(table) == n
    # touch the oldest: the second oldest is now first out
    assert table.factory_for(schemas[0], byte_tok) == (first[0], "hit")
    table.factory_for(schemas[n], byte_tok)
    assert len(table) == n and len(counting_builder) == n + 1
    assert table.factory_for(schemas[0], byte_tok) == (first[0], "hit")
    rebuilt, how = table.factory_for(schemas[1], byte_tok)
    assert how == "miss" and rebuilt is not first[1]
    assert len(table) == n and len(counting_builder) == n + 2
    # a factory a job still holds outlives its entry
    assert first[1]().allowed_tokens().any()


def test_bound_is_a_constant_not_a_knob():
    from sutro_tpu.engine.config import EngineConfig

    assert FactoryTable.MAX_ENTRIES == 4
    assert not [f for f in EngineConfig.__dataclass_fields__ if "constraint" in f]


# ---------------------------------------------------------------------------
# (f) the spans stay on a hit, with the cache attr; the counter's labels
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warm_then_hit_job(live_engine):
    """A schema's second job on the shared engine: both job-scope
    lookups are hits. Returns (job id, spans recorded since its submit,
    counter deltas over the two jobs)."""
    eng, _url, _home = live_engine
    schema = _schema("spans")
    before = _counts()
    first = _submit(eng, _REVIEWS[:2], output_schema=schema)
    assert _wait_terminal(eng, first) == JobStatus.SUCCEEDED
    mark = time.monotonic() - telemetry.RECORDER.epoch_mono
    second = _submit(eng, _REVIEWS[:2], output_schema=schema)
    assert _wait_terminal(eng, second) == JobStatus.SUCCEEDED
    spans = [
        s for s in telemetry.RECORDER.snapshot()
        if s["t0_s"] >= mark - 1e-3
        and s["name"] in ("constraint_prep", "constraint_compile")
        and (s.get("attrs") or {}).get("scope") == "job"
    ]
    return second, spans, _delta(before)


@pytest.mark.parametrize("site", ["submit", "session"])
def test_job_scope_span_on_a_hit(warm_then_hit_job, site):
    job_id, spans, _ = warm_then_hit_job
    if site == "submit":
        mine = [s for s in spans if s["attrs"].get("thread") == "submit"]
        assert [s["name"] for s in mine] == ["constraint_prep"]
    else:
        mine = [s for s in spans if s["job_id"] == job_id]
        assert len(mine) == 1
        assert mine[0]["name"] in ("constraint_compile", "constraint_prep")
        assert mine[0]["attrs"]["rows"] == 2
    span = mine[0]
    assert span["attrs"]["cache"] == "hit"
    # the true duration of a lookup: positive, and nowhere near a build
    assert 0.0 < span["dur_s"] < 0.05


@pytest.mark.parametrize("result", RESULTS)
def test_counter_counts_each_result(warm_then_hit_job, byte_tok, result):
    _job, _spans, over_two_jobs = warm_then_hit_job
    if result != "wait":
        # two jobs of one schema: one build, three lookups served by it
        assert over_two_jobs[result] == {"miss": 1.0, "hit": 3.0}[result]
        return
    assert over_two_jobs["wait"] == 0.0
    table, release = FactoryTable(), threading.Event()
    real, started = fsm.schema_constraint_factory, threading.Event()

    def slow(schema, tokenizer):
        started.set()
        assert release.wait(30)
        return real(schema, tokenizer)

    mp = pytest.MonkeyPatch()
    mp.setattr(fsm, "schema_constraint_factory", slow)
    try:
        before = _counts()
        threads = [
            threading.Thread(
                target=table.factory_for, args=(_schema("count-wait"), byte_tok)
            )
            for _ in range(3)
        ]
        threads[0].start()
        assert started.wait(30)
        for t in threads[1:]:
            t.start()
        time.sleep(0.2)
        release.set()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
        assert _delta(before) == {"hit": 0.0, "miss": 1.0, "wait": 2.0}
    finally:
        mp.undo()
    text = telemetry.REGISTRY.to_prometheus()
    for label in RESULTS:
        assert f'sutro_constraint_factory_total{{result="{label}"}}' in text
