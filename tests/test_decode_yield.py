"""What a decode dispatch yields (OBSERVABILITY.md): on each of the four
decode paths, row-steps dispatched = tokens committed + row-steps lost,
summed over reasons: in the registry's counters, on the ``accept``
spans and in the job's record; and counting changes no token. And what
the scheduler makes of it: a constrained greedy batch whose unmasked
tokens its FSMs accept stays on speculative windows, one whose unmasked
tokens are refused goes to masked single steps and comes back, with the
same tokens and the same sums whichever path it takes (rigged logits)."""

import itertools
import time

import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.constrain import schema_constraint_factory
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import (
    ContinuousBatcher,
    GenRequest,
    JobCtx,
)
from sutro_tpu.models.configs import MODEL_CONFIGS

# a forced scaffold (the verify forward commits it), an enum leaf, then
# free text, where the window's unmasked samples are refused
SCHEMA = {
    "type": "object",
    "properties": {
        "classification_result": {
            "type": "string", "enum": ["positive", "negative"],
        },
        "note": {"type": "string", "maxLength": 12},
    },
    "required": ["classification_result", "note"],
}
PLAIN_TEXTS = ["first row", "second", "third one"]
PATHS = ("pipelined", "window", "fastforward", "single")
COUNTERS = (
    "sutro_sched_row_steps_total",
    "sutro_sched_tokens_committed_total",
    "sutro_sched_row_steps_lost_total",
)
ITERATIONS = "sutro_sched_iterations_total"
# path -> the row-steps a row of one dispatch (decode_multi_step 8,
# constrain_fastforward 16 + 1)
WIDTH = {"pipelined": 8, "window": 8, "fastforward": 17, "single": 1}


def _requests(tok, scenario, wrap=None):
    """``wrap``: what each row's FSM is handed to the scheduler as."""
    def req(i, text, **kw):
        if wrap is not None and kw.get("constraint") is not None:
            kw["constraint"] = wrap(i, kw["constraint"])
        return GenRequest(
            row_id=i, prompt_ids=np.array(tok.encode(text), np.int32), **kw
        )

    if scenario == "plain":
        # three plain rows and one with a stop sequence that never
        # comes (the per-token loop), with pages for several windows
        # each; _run makes one row's early token a stop id, so with two
        # windows in flight the window behind that row's end finds the
        # row gone
        rows = [
            req(i, t, max_new_tokens=43, temperature=0.0)
            for i, t in enumerate(PLAIN_TEXTS)
        ]
        return rows + [
            req(3, "the fourth", max_new_tokens=43, temperature=0.0,
                stop_seqs=[b"\xfe\xff\xfe"])
        ]
    factory = schema_constraint_factory(SCHEMA, tok)
    if scenario == "tight":
        # sampled rows whose budget covers the shortest output and three
        # tokens more: it bites inside the free text
        return [
            req(i, t, max_new_tokens=factory().min_tokens() + 4,
                temperature=0.8, constraint=factory())
            for i, t in enumerate(["first row", "second", "third one"])
        ]
    if scenario in ("scaffold", "steps"):
        rows = [
            req(i, t, max_new_tokens=80, temperature=0.0,
                constraint=factory())
            for i, t in enumerate(["first row", "second", "third one"])
        ]
        # a plain greedy row rides the verify forward with no plan
        return rows + [
            req(100, "plain rider", max_new_tokens=12, temperature=0.0)
        ]
    assert scenario == "sampled"
    return [
        req(i, t, max_new_tokens=80, temperature=0.8, constraint=factory())
        for i, t in enumerate(["first row", "second"])
    ]


_RUN_IDS = itertools.count()
_RUNS = {}


def _series(snap, name):
    return dict((snap.get(name) or {}).get("series", {}))


def _gained(before, after):
    """{counter: {series: n}} of what the yield counters and the
    iterations gained between two collects of the registry."""
    gained = {}
    for name in COUNTERS + (ITERATIONS,):
        a, z = _series(before, name), _series(after, name)
        gained[name] = {
            k: int(z[k] - a.get(k, 0)) for k in z if z[k] != a.get(k, 0)
        }
    return gained


def _by_path(gained):
    """{path: {iterations, row_steps, committed, lost: {reason: n}}} of
    what the registry's series gained over a run."""
    out = {}
    for key, name in (("iterations", ITERATIONS), ("row_steps", COUNTERS[0]),
                      ("committed", COUNTERS[1])):
        for path, n in gained[name].items():
            out.setdefault(path, {"iterations": 0, "row_steps": 0,
                                  "committed": 0, "lost": {}})[key] = n
    for k, n in gained[COUNTERS[2]].items():
        path, reason = k.split(",")
        out[path]["lost"][reason] = n
    return out


def _lost_total(tallies):
    total = {}
    for y in tallies:
        for reason, n in y["lost"].items():
            total[reason] = total.get(reason, 0) + n
    return total


def _early_stop(tok):
    """A token the greedy model gives ONE of the plain rows inside its
    first window and no row before it: as a stop id it ends that row
    there while the others decode on. Learned from a run without it
    (greedy decoding repeats itself to the token)."""
    if "stop" not in _RUNS:
        out = [r.token_ids for _, r in sorted(_run(tok, "plain")[1].items())]
        _RUNS["stop"] = next(
            t for row in out for t in row[2:7]
            if all(t not in other[:24] for other in out if other is not row)
            and t not in row[:2]
        )
    return _RUNS["stop"]


def _run(tok, scenario, tel=True, extra_stops=(), wrap=None, spy=None):
    """One run of a scenario on a fresh batcher: the job's own tallies
    (``JobCtx.stats``), its results, what the registry's counters gained
    by path (``_by_path``) and the ``accept`` and ``fsm_mask`` spans. The
    ``scaffold``
    scenario is HELD on windows, as a batch whose unmasked tokens verify
    is: these random weights' are refused, and left to the scheduler's
    rule the batch would take masked steps (the rigged runs below);
    ``steps`` is the same job held on masked steps. ``wrap`` is
    ``_requests``'s; ``spy`` (a list) gains ``(site, masks)`` for every
    dispatch that may carry FSM masks: ``step``, ``window``, ``admit``."""
    from sutro_tpu.engine import scheduler as sched_mod

    was = telemetry.enabled()
    telemetry.set_enabled(tel)
    mp = pytest.MonkeyPatch()
    if scenario in ("scaffold", "steps"):
        gain = 2.0 if scenario == "scaffold" else 0.0
        mp.setattr(sched_mod, "_window_gain", lambda p, K, c: gain)
    if spy is not None:
        step, window = ModelRunner.decode_step, ModelRunner.decode_window
        admit = sched_mod._admit_sample_jit

        def spied(site, fn, pick):
            def call(*a, **kw):
                spy.append((site, pick(a, kw)))
                return fn(*a, **kw)
            return call

        mp.setattr(ModelRunner, "decode_step", spied(
            "step", step, lambda a, kw: kw.get("allowed")))
        mp.setattr(ModelRunner, "decode_window", spied(
            "window", window, lambda a, kw: kw.get("allowed0")))
        mp.setattr(sched_mod, "_admit_sample_jit", spied(
            "admit", admit, lambda a, kw: a[5]))
    try:
        ecfg = EngineConfig(
            kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
            decode_batch_size=4, use_pallas=False, param_dtype="float32",
            activation_dtype="float32", decode_multi_step=8,
            decode_lookahead=2, constrain_fastforward=16,
        )
        b = ContinuousBatcher(
            ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
            stop_ids=list(tok.stop_ids()) + list(extra_stops),
            token_bytes=tok.token_bytes, seed=11,
        )
        before = telemetry.REGISTRY.collect()
        job_id = f"yield-{scenario}-{next(_RUN_IDS)}"
        res = {}
        ctx = JobCtx(
            job_id=job_id, pending=_requests(tok, scenario, wrap),
            on_result=lambda r: res.__setitem__(r.row_id, r),
        )
        assert b.run_multi(
            [ctx], on_job_done=lambda c, outcome: None
        ) == "completed"
        gained = _gained(before, telemetry.REGISTRY.collect())
        spans = [
            s for s in telemetry.RECORDER.snapshot(job_id)
            if s["name"] in ("accept", "fsm_mask")
        ]
        return dict(ctx.stats), res, _by_path(gained), spans
    finally:
        mp.undo()
        telemetry.set_enabled(was)


def _stops(tok, scenario):
    return (_early_stop(tok),) if scenario == "plain" else ()


def _scenario(tok, scenario):
    if scenario not in _RUNS:
        _RUNS[scenario] = _run(
            tok, scenario, extra_stops=_stops(tok, scenario)
        )
    return _RUNS[scenario]


# path -> the scenario that takes it, and the reasons it must show
CASES = {
    "pipelined": ("plain", {"finished", "stale"}),
    "window": ("scaffold", {"rejected"}),
    "fastforward": ("scaffold", {"no_plan"}),
    "single": ("sampled", set()),
}


@pytest.mark.parametrize("path", PATHS)
def test_row_steps_are_the_committed_and_the_lost(path, byte_tok):
    scenario, reasons = CASES[path]
    _, _, by_path, _ = _scenario(byte_tok, scenario)
    y = by_path[path]
    assert y["row_steps"] > 0 and y["committed"] > 0
    assert y["row_steps"] == y["committed"] + sum(y["lost"].values()), y
    assert reasons <= set(y["lost"]), y
    assert all(n > 0 for n in y["lost"].values()), y
    if path == "fastforward":
        # a plan shorter than the forward, or one the model left
        assert {"plan_short", "diverged"} & set(y["lost"]), y
    if path == "single":
        assert y["lost"] == {} and y["row_steps"] == y["committed"]
    # only decode paths were counted (an idle iteration yields nothing)
    assert {p for p, t in by_path.items() if t["row_steps"]} <= set(PATHS)


@pytest.mark.parametrize("path", PATHS)
def test_a_paths_row_steps_are_its_dispatches_widths(path, byte_tok):
    """Row-steps come a dispatch's width a row: whole widths, and no
    more than the path's iterations could have dispatched to a full
    batch of four."""
    scenario, _ = CASES[path]
    y = _scenario(byte_tok, scenario)[2][path]
    assert y["row_steps"] % WIDTH[path] == 0, y
    assert 0 < y["row_steps"] <= y["iterations"] * 4 * WIDTH[path], y


@pytest.mark.parametrize("scenario", ["plain", "scaffold", "sampled"])
def test_committed_tokens_are_the_tokens_the_results_hold(
    scenario, byte_tok
):
    _, res, by_path, _ = _scenario(byte_tok, scenario)
    stops = set(byte_tok.stop_ids())
    held = 0
    for r in res.values():
        # a result leaves out the stop id its row ended on, and holds
        # the first token, which the prefill sampled
        ended_on_stop = (
            r.finish_reason == "stop"
            and not (r.token_ids and r.token_ids[-1] in stops)
        )
        held += len(r.token_ids) + int(ended_on_stop) - 1
    assert sum(y["committed"] for y in by_path.values()) == held


@pytest.mark.parametrize("scenario", ["plain", "scaffold", "sampled"])
def test_a_jobs_own_tallies_are_the_registrys_when_it_runs_alone(
    scenario, byte_tok
):
    stats, _, by_path, _ = _scenario(byte_tok, scenario)
    want = by_path.values()
    assert stats["row_steps"] == sum(y["row_steps"] for y in want)
    assert {
        k[len("lost_"):]: v for k, v in stats.items()
        if k.startswith("lost_")
    } == _lost_total(want)


@pytest.mark.parametrize("scenario", ["plain", "scaffold", "sampled"])
def test_counting_changes_no_token(scenario, byte_tok):
    stats_on, on, _, _ = _scenario(byte_tok, scenario)
    stats_off, off, gained, spans = _run(
        byte_tok, scenario, tel=False, extra_stops=_stops(byte_tok, scenario)
    )
    assert {
        i: (tuple(r.token_ids), r.finish_reason) for i, r in on.items()
    } == {
        i: (tuple(r.token_ids), r.finish_reason) for i, r in off.items()
    }
    # off: nothing reaches the registry or the recorder; the job's
    # record is still kept, and is the same
    assert gained == {} and spans == []
    assert stats_off == stats_on


@pytest.mark.parametrize("scenario", ["plain", "scaffold", "sampled"])
def test_the_accept_spans_carry_each_dispatchs_yield(scenario, byte_tok):
    _, _, by_path, spans = _scenario(byte_tok, scenario)
    carried = [
        s["attrs"] for s in spans if "row_steps" in s.get("attrs", {})
    ]
    assert carried
    want = by_path.values()
    assert sum(a["row_steps"] for a in carried) == sum(
        y["row_steps"] for y in want
    )
    assert sum(a["tokens"] for a in carried) == sum(
        y["committed"] for y in want
    )
    for a in carried:
        assert a["row_steps"] == a["tokens"] + sum(
            a.get("lost", {}).values()
        ), a
        assert all(n > 0 for n in a.get("lost", {}).values()), a
    assert _lost_total(
        {"lost": a.get("lost", {})} for a in carried
    ) == _lost_total(want)


def test_a_failed_row_loses_its_steps_and_the_sum_still_holds(byte_tok):
    """A row whose decode raises (the fault plan's ``row.decode``) is
    released with its token unrecorded: its steps are ``failed``."""
    from sutro_tpu.engine import faults

    faults.configure("row.decode:error:times=1")
    try:
        stats, res, by_path, _ = _run(byte_tok, "plain")
    finally:
        faults.clear()
    y = by_path["pipelined"]
    assert y["lost"].get("failed", 0) > 0, y
    assert y["row_steps"] == y["committed"] + sum(y["lost"].values()), y
    assert stats["lost_failed"] == y["lost"]["failed"]
    assert any(r.finish_reason == "error" for r in res.values())


def test_the_job_record_carries_its_rows_yield(
    tiny_ecfg, byte_tok, tmp_path, monkeypatch
):
    """``perf["decode_yield"]`` of a constrained job: why it is slow,
    without a profiler; ``fastforward.forced_tokens`` stays beside it."""
    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    from sutro_tpu.engine.api import LocalEngine
    from sutro_tpu.interfaces import JobStatus

    eng = LocalEngine(tiny_ecfg)
    try:
        job_id = eng.submit_batch_inference(
            {"model": "tiny-dense", "inputs": ["a review", "another"],
             "output_schema": SCHEMA,
             "sampling_params": {"max_new_tokens": 80, "temperature": 0.0}}
        )
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if JobStatus(eng.job_status(job_id)).is_terminal():
                break
            time.sleep(0.2)
        rec = eng.get_job(job_id)
    finally:
        eng.close(timeout=10)
    assert rec["status"] == "SUCCEEDED", rec.get("failure_reason")
    y = rec["perf"]["decode_yield"]
    assert y["row_steps"] == y["committed"] + sum(y["lost"].values())
    # random weights: the verify forward over the opening scaffold finds
    # the rows' unmasked tokens invalid, the job takes masked steps, and
    # no window is refused; what is lost is a forward's unfilled width
    assert y["unmasked"]["asked"] > 0
    assert y["unmasked"]["ok"] < 0.5 * y["unmasked"]["asked"], y
    assert y["lost"].get("rejected", 0) == 0, y
    assert y["lost"].get("plan_short", 0) > 0, y
    assert 0 < y["committed"] < y["row_steps"]
    assert rec["perf"]["fastforward"]["forced_tokens"] > 0
    # every token of the job but its rows' first came from a dispatch
    assert y["committed"] <= rec["output_tokens"] + 2


# ---------------------------------------------------------------------
# window or masked step, from the share of unmasked tokens accepted
# ---------------------------------------------------------------------

# the token the rigged model's UNMASKED argmax always is
FAVOURITE = 65
# rigging -> does a row that has emitted ``n`` tokens refuse FAVOURITE?
RIGGINGS = {
    "always-valid": lambda n: False,
    "never-valid": lambda n: True,
    # refused for a row's first 30 tokens, accepted from there on
    "turns-valid": lambda n: n < 30,
    # and the other way
    "turns-invalid": lambda n: n >= 30,
}
# what holds a run on one path whatever it observes: _window_gain's value
HOLDS = {"window": 2.0, "single": 0.0}


class _Rigged:
    """An FSM that accepts every token but, where the rigging says so,
    the model's favourite. It has no forced run: the fast-forward probe
    finds no plan, and every iteration is a window or a masked step."""

    def __init__(self, vocab, refuses):
        self.vocab, self.refuses, self.n = vocab, refuses, 0

    def allowed_tokens(self):
        m = np.ones((self.vocab,), bool)
        m[FAVOURITE] = not self.refuses(self.n)
        return m

    def token_allowed(self, tok):
        return tok != FAVOURITE or not self.refuses(self.n)

    def advance(self, tok):
        self.n += 1

    def is_complete(self):
        return False

    def min_tokens(self):
        return 1


def _rigged_run(tok, rigging, hold=None):
    """One job of four greedy constrained rows on a model whose logits
    are rigged so that its unmasked argmax is FAVOURITE at every
    position (every forward's logits, prefill, window, step and verify
    alike), under ``_Rigged`` FSMs; ``hold`` keeps the scheduler's rule
    on one path. Returns the path of each iteration in order, the
    results, the registry's gains by path, the job's tallies and the
    ``unmasked_ok`` attrs of its ``decode_window`` spans."""
    key = (rigging, hold)
    if key in _RUNS:
        return _RUNS[key]
    from sutro_tpu.engine import runner as runner_mod
    from sutro_tpu.engine import scheduler as sched_mod

    mp = pytest.MonkeyPatch()
    forward = runner_mod.transformer.forward

    def rigged_forward(*a, **kw):
        logits, *rest = forward(*a, **kw)
        return (logits.at[..., FAVOURITE].add(1e4), *rest)

    paths = []
    after = ContinuousBatcher._after_step

    def recording(self, live, on_job_done, path, n_active):
        paths.append(path)
        return after(self, live, on_job_done, path, n_active)

    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        mp.setattr(runner_mod.transformer, "forward", rigged_forward)
        mp.setattr(ContinuousBatcher, "_after_step", recording)
        if hold is not None:
            mp.setattr(
                sched_mod, "_window_gain", lambda p, K, c: HOLDS[hold]
            )
        ecfg = EngineConfig(
            kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
            decode_batch_size=4, use_pallas=False, param_dtype="float32",
            activation_dtype="float32", decode_multi_step=8,
            decode_lookahead=2, constrain_fastforward=16,
        )
        b = ContinuousBatcher(
            ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
            stop_ids=tok.stop_ids(), seed=11,
        )
        vocab = MODEL_CONFIGS["tiny-dense"].vocab_size
        before = telemetry.REGISTRY.collect()
        job_id = f"rigged-{rigging}-{hold}-{next(_RUN_IDS)}"
        res = {}
        ctx = JobCtx(
            job_id=job_id,
            pending=[
                GenRequest(
                    row_id=i, prompt_ids=np.array(tok.encode(t), np.int32),
                    max_new_tokens=65, temperature=0.0,
                    constraint=_Rigged(vocab, RIGGINGS[rigging]),
                )
                for i, t in enumerate(
                    ["first row", "second", "third one", "the fourth"]
                )
            ],
            on_result=lambda r: res.__setitem__(r.row_id, r),
        )
        assert b.run_multi(
            [ctx], on_job_done=lambda c, outcome: None
        ) == "completed"
        gained = _gained(before, telemetry.REGISTRY.collect())
        seen = [
            s["attrs"].get("unmasked_ok")
            for s in telemetry.RECORDER.snapshot(job_id)
            if s["name"] == "decode_window"
        ]
    finally:
        mp.undo()
        telemetry.set_enabled(was)
    _RUNS[key] = (paths, res, _by_path(gained), dict(ctx.stats), seen)
    return _RUNS[key]


def _tokens(res):
    return {
        i: (tuple(r.token_ids), r.finish_reason) for i, r in res.items()
    }


@pytest.mark.parametrize("rigging", sorted(RIGGINGS))
def test_a_batch_takes_the_path_its_unmasked_tokens_earn(rigging, byte_tok):
    paths, res, _, stats, seen = _rigged_run(byte_tok, rigging)
    assert len(res) == 4 and set(paths) <= {"window", "single"}
    text = "".join(p[0] for p in paths)  # "w" a window, "s" a step
    if rigging == "always-valid":
        # every unmasked token verifies: windows to the end, each
        # committing its whole width
        assert "s" not in text and len(text) <= 10, text
        assert stats["unmasked_ok"] == stats["unmasked_asked"] > 0
    elif rigging == "never-valid":
        # at most two windows, then masked steps to the end
        assert text.lstrip("w") == "s" * (len(text) - text.count("w")), text
        assert 1 <= text.count("w") <= 2 and text.count("s") > 50, text
        assert stats["unmasked_ok"] == 0 < stats["unmasked_asked"]
    elif rigging == "turns-valid":
        # steps while FAVOURITE is refused, and BACK to windows a few
        # steps after it is not: what a masked step says of its rows'
        # unmasked argmax is what brings the batch back
        head, _, tail = text.partition("sw")
        assert 1 <= head.count("w") <= 2 and head.lstrip("w") == "s" * (
            len(head) - head.count("w")
        ), text
        assert tail and set(tail) == {"w"}, text
        assert 25 <= text.count("s") <= 40, text
    else:
        # windows while it verifies, steps from the refusal on
        assert text.rstrip("s").count("s") == 0, text
        assert 3 <= text.count("w") <= 6 and text.count("s") > 25, text
    # the span says what the choice was made from: the estimate before
    # the dispatch, 1.0 before any observation
    assert seen and seen[0] == 1.0 and all(
        v is not None and 0.0 <= v <= 1.0 for v in seen
    )
    assert len(seen) >= len(paths)


@pytest.mark.parametrize("hold", sorted(HOLDS))
@pytest.mark.parametrize("rigging", sorted(RIGGINGS))
def test_a_jobs_tokens_are_the_same_whichever_path_takes_them(
    rigging, hold, byte_tok
):
    _, chosen, _, _, _ = _rigged_run(byte_tok, rigging)
    paths, held, _, _, _ = _rigged_run(byte_tok, rigging, hold)
    # (held on windows, a row's last tokens have room for less than a
    # window and take single steps, as any batch's do)
    text = "".join(p[0] for p in paths).rstrip("s" if hold == "window" else "")
    assert set(text) == {hold[0]} and len(paths) - len(text) < 8, paths
    assert _tokens(chosen) == _tokens(held)
    for i, r in chosen.items():
        assert r.cumulative_logprob == pytest.approx(
            held[i].cumulative_logprob, rel=1e-4, abs=1e-3
        )
        # what the FSM refused is in no result
        refuses = RIGGINGS[rigging]
        assert not any(
            t == FAVOURITE and refuses(n)
            for n, t in enumerate(r.token_ids)
        )


@pytest.mark.parametrize("hold", [None] + sorted(HOLDS))
@pytest.mark.parametrize("rigging", sorted(RIGGINGS))
def test_row_steps_add_up_on_every_path_across_a_switch(
    rigging, hold, byte_tok
):
    paths, res, by_path, stats, _ = _rigged_run(byte_tok, rigging, hold)
    assert {p for p, y in by_path.items() if y["row_steps"]} == set(paths)
    for path, y in by_path.items():
        assert y["row_steps"] == y["committed"] + sum(y["lost"].values()), (
            path, y,
        )
        assert y["iterations"] == paths.count(path)
        assert y["row_steps"] % WIDTH[path] == 0
    # every token of a row but its first came from one of them
    assert sum(y["committed"] for y in by_path.values()) == sum(
        len(r.token_ids) - 1 for r in res.values()
    )
    assert stats["row_steps"] == sum(
        y["row_steps"] for y in by_path.values()
    )
    if "single" in by_path:
        assert by_path["single"]["lost"] == {}
    if rigging == "never-valid" and hold is None:
        # the share the issue is about: nearly every row-step a token
        kept = sum(y["committed"] for y in by_path.values()) / stats[
            "row_steps"
        ]
        assert kept > 0.8, by_path


# ---------------------------------------------------------------------------
# The FSM masks travel bit-packed from the mask cache to the device: the
# same tokens as the bool masks gave, no [B, V] bool array on the way,
# and a counter of how each constrained row's mask was come by.
# ---------------------------------------------------------------------------

MASK_ROWS = "sutro_fsm_mask_rows_total"
# scenario -> what takes its masks: a window's ``allowed0`` behind a
# refusal and verify forwards (greedy); masked single steps of sampled
# rows under the batcher's seed; the greedy job on masked steps; sampled
# rows whose budget bites. Every one samples its first tokens under masks.
MASKED = ("scaffold", "sampled", "steps", "tight")


class _BoolOnly:
    """A ``TokenFSM`` behind the surface a constraint had before the
    packed answer: ``allowed_tokens`` in bools, and no ``allowed_packed``.
    The scheduler packs its row where it assembles the masks, which is
    what every row's went through before (``np.packbits`` of the bools)."""

    def __init__(self, fsm):
        self._fsm = fsm
        for name in ("token_allowed", "advance", "is_complete",
                     "min_tokens", "plan_fastforward"):
            setattr(self, name, getattr(fsm, name))

    def allowed_tokens(self, remaining=None):
        return self._fsm.allowed_tokens(remaining=remaining)


def _masked(tok, scenario, bool_only=False):
    """``_run`` of a masked scenario, with what the dispatches were
    handed and what ``sutro_fsm_mask_rows_total`` gained, by path."""
    key = ("masked", scenario, bool_only)
    if key not in _RUNS:
        spy = []
        before = _series(telemetry.REGISTRY.collect(), MASK_ROWS)
        out = _run(
            tok, scenario, spy=spy,
            wrap=(lambda i, c: _BoolOnly(c)) if bool_only else None,
        )
        after = _series(telemetry.REGISTRY.collect(), MASK_ROWS)
        rows = {
            k: int(after[k] - before.get(k, 0)) for k in after
            if after[k] != before.get(k, 0)
        }
        _RUNS[key] = out + (spy, rows)
    return _RUNS[key]


@pytest.mark.parametrize("scenario", MASKED)
def test_packed_masks_change_no_token(scenario, byte_tok):
    """A constrained job decodes token for token what it decoded while
    its masks were [B, V] bools packed at the runner: greedy and sampled
    (a fixed seed), through the masked step, a window's ``allowed0``
    and the first-token sampling."""
    _, packed, by_path, _, spy, _ = _masked(byte_tok, scenario)
    _, bools, _, _, _, _ = _masked(byte_tok, scenario, bool_only=True)
    assert _tokens(packed) == _tokens(bools)
    for i, r in packed.items():
        assert r.cumulative_logprob == pytest.approx(
            bools[i].cumulative_logprob, rel=1e-5, abs=1e-5
        )
    constrained = [r for i, r in packed.items() if i < 100]
    assert all(r.finish_reason == "schema_complete" for r in constrained)
    # the scenario went the way it is here for
    sites = {site for site, masks in spy if masks is not None}
    assert "admit" in sites
    if scenario == "scaffold":
        assert "window" in sites and by_path["fastforward"]["committed"]
    else:
        assert "step" in sites and by_path["single"]["committed"]


@pytest.mark.parametrize("bool_only", [False, True])
@pytest.mark.parametrize("scenario", MASKED)
def test_the_device_is_handed_bit_packed_masks(scenario, bool_only, byte_tok):
    """Whatever the constraint answers in, what reaches a device program
    is uint8 [B, ceil(V / 8)]: the host holds no [B, V] bool array."""
    spy = _masked(byte_tok, scenario, bool_only)[4]
    V = MODEL_CONFIGS["tiny-dense"].vocab_size
    handed = [(site, m) for site, m in spy if m is not None]
    assert handed
    for site, m in handed:
        assert isinstance(m, np.ndarray) and m.dtype == np.uint8, site
        assert m.ndim == 2 and m.shape[1] == (V + 7) // 8, (site, m.shape)
        if site != "admit":  # (a prefill bucket has its own row count)
            assert m.shape[0] == 4
        # a row is a constrained row's mask or all ones, the bits past
        # the vocabulary zero
        ones = np.packbits(np.ones((V,), bool))
        rows = np.unpackbits(m, axis=1, count=V)
        assert all(
            r.sum() < V or np.array_equal(m[i], ones)
            for i, r in enumerate(rows)
        )


@pytest.mark.parametrize("scenario", MASKED)
def test_mask_rows_count_every_constrained_row_masked(scenario, byte_tok):
    """``sutro_fsm_mask_rows_total`` sums to the constrained rows the
    mask assembly wrote (a masked step's constrained row-steps; a
    window's flagged rows), ``cached`` wherever the budget does not
    bite, and ``packed_here`` for a constraint that answers in bools."""
    _, _, by_path, _, spy, rows = _masked(byte_tok, scenario)
    V = MODEL_CONFIGS["tiny-dense"].vocab_size
    ones = np.packbits(np.ones((V,), bool))
    written = sum(
        int((m != ones).any(axis=1).sum())
        for site, m in spy if m is not None and site != "admit"
    )
    assert sum(rows.values()) == written > 0
    if scenario in ("sampled", "tight"):
        # every row of these jobs is constrained: a row-step, a mask
        assert written == by_path["single"]["row_steps"]
    assert rows.get("packed_here", 0) == 0
    if scenario == "tight":
        assert rows["filtered"] > 0 and rows["cached"] > 0
    else:
        assert set(rows) == {"cached"}
    _, _, _, _, _, bools = _masked(byte_tok, scenario, bool_only=True)
    assert bools == {"packed_here": written}


def test_the_fsm_mask_span_says_how_many_rows_the_cache_served(byte_tok):
    """``fsm_mask`` spans carry ``rows`` and ``cached``: summed over a
    job they are the counter's (the first-token sampler's spans count no
    row of a decode dispatch and carry no ``cached``)."""
    for scenario in ("tight", "sampled"):
        _, _, _, spans, _, rows = _masked(byte_tok, scenario)
        masks = [
            s["attrs"] for s in spans
            if s["name"] == "fsm_mask" and "cached" in s["attrs"]
        ]
        assert masks and all(a["cached"] <= a["rows"] for a in masks)
        assert sum(a["cached"] for a in masks) == rows["cached"]


class _RaisesPacked:
    """A ``TokenFSM`` whose packed answer raises from its ``n``-th ask."""

    def __init__(self, fsm, n):
        self._fsm, self._left = fsm, n
        for name in ("allowed_tokens", "token_allowed", "advance",
                     "is_complete", "min_tokens", "plan_fastforward"):
            setattr(self, name, getattr(fsm, name))

    def allowed_packed(self, remaining=None, shared=None):
        self._left -= 1
        if self._left < 0:
            raise RuntimeError("this row's FSM broke")
        return self._fsm.allowed_packed(remaining=remaining, shared=shared)


def test_a_constraint_that_raises_fails_its_own_slot_only(byte_tok):
    """Row isolation in the mask assembly: the row whose packed answer
    raises ends in ``error``; the others decode what they decode in the
    run where nothing raises (greedy rows: a row's tokens are its own)."""
    _, clean, _, _, _, _ = _masked(byte_tok, "steps")
    _, res, by_path, _ = _run(
        byte_tok, "steps",
        wrap=lambda i, c: _RaisesPacked(c, 3) if i == 1 else c,
    )
    assert res[1].finish_reason == "error"
    others = {i: r for i, r in res.items() if i != 1}
    assert _tokens(others) == {
        i: t for i, t in _tokens(clean).items() if i != 1
    }
    y = by_path["single"]
    assert y["lost"].get("failed", 0) >= 1
    assert y["row_steps"] == y["committed"] + sum(y["lost"].values()), y
