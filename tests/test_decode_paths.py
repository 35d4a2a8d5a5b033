"""The scheduler chooses a decode path in one place
(``ContinuousBatcher._choose_path``): a table of facts -> path with one
case a gate, the facts ``_build_batch`` reads off real rows and the
labels those runs are counted under, and a configuration that names a
field the engine no longer has."""

import json

import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig, load_engine_config
from sutro_tpu.engine.constrain import schema_constraint_factory
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import (
    ContinuousBatcher,
    GenRequest,
    _DecodeFacts,
)
from sutro_tpu.models.configs import MODEL_CONFIGS

# a forced scaffold (the fast-forward engages), then free text (the
# probe fails, the window runs)
ENUMS = {
    "type": "object",
    "properties": {
        "label": {"type": "string", "enum": ["positive", "negative"]},
        "note": {"type": "string", "maxLength": 12},
    },
    "required": ["label", "note"],
}
TEXTS = ["first row", "second", "third one"]

_BATCHERS = {}


def _batcher(byte_tok, **engine_kw):
    """One batcher a configuration: building one compiles nothing."""
    key = tuple(sorted(engine_kw.items()))
    if key not in _BATCHERS:
        base = dict(
            kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
            decode_batch_size=4, use_pallas=False, param_dtype="float32",
            activation_dtype="float32",
        )
        base.update(engine_kw)
        _BATCHERS[key] = ContinuousBatcher(
            ModelRunner(MODEL_CONFIGS["tiny-dense"], EngineConfig(**base)),
            stop_ids=byte_tok.stop_ids(),
        )
    return _BATCHERS[key]


# a sampled batch of plain rows with room for many windows
PLAIN = _DecodeFacts(
    has_constraint=False, has_row_seed=False, has_penalty=False,
    all_greedy=False, constrained_greedy=True, flagged=False, room=200,
    wrapped=False,
)
GREEDY_SCHEMA = PLAIN._replace(has_constraint=True, all_greedy=True)

# case -> (engine config, facts, windows in flight, the path to try)
CHOICES = {
    "sampled-and-plain": ({}, PLAIN, 0, "pipelined"),
    "sampled-and-plain-windows-in-flight": ({}, PLAIN, 2, "pipelined"),
    "greedy-and-plain": ({}, PLAIN._replace(all_greedy=True), 0, "pipelined"),
    "constrained-greedy": ({}, GREEDY_SCHEMA, 0, "fastforward"),
    "constrained-greedy-beside-sampled-plain-rows": (
        {}, GREEDY_SCHEMA._replace(all_greedy=False), 0, "window",
    ),
    "constrained-sampled": (
        {},
        PLAIN._replace(has_constraint=True, constrained_greedy=False),
        0, "single",
    ),
    "a-seeded-row": ({}, PLAIN._replace(has_row_seed=True), 0, "single"),
    "a-seeded-row-beside-a-schema": (
        {}, GREEDY_SCHEMA._replace(has_row_seed=True), 0, "single",
    ),
    "a-penalised-row": ({}, PLAIN._replace(has_penalty=True), 0, "single"),
    "a-penalised-row-beside-a-schema": (
        {}, GREEDY_SCHEMA._replace(has_penalty=True), 0, "single",
    ),
    # the window masks a flagged row's first step (allowed0); the
    # probe ahead of it sends a flagged row it cannot plan for there
    "a-flagged-row-with-a-constraint": (
        {}, GREEDY_SCHEMA._replace(flagged=True), 0, "fastforward",
    ),
    # nothing else clears the flag of a slot whose batch has no schema
    "a-flagged-row-without-a-constraint": (
        {}, PLAIN._replace(flagged=True), 0, "single",
    ),
    "room-under-one-window": ({}, PLAIN._replace(room=7), 0, "single"),
    "room-for-just-one-window": ({}, PLAIN._replace(room=8), 0, "pipelined"),
    "room-under-one-window-with-a-schema": (
        {}, GREEDY_SCHEMA._replace(room=7), 0, "single",
    ),
    # what is in flight is fetched; the refill asks for room itself
    "room-under-one-window-windows-in-flight": (
        {}, PLAIN._replace(room=3), 1, "pipelined",
    ),
    "sp-above-one-with-a-schema": (
        {}, GREEDY_SCHEMA._replace(wrapped=True), 0, "window",
    ),
    "sp-above-one-and-plain": ({}, PLAIN._replace(wrapped=True), 0, "pipelined"),
    "decode-multi-step-1": (dict(decode_multi_step=1), PLAIN, 0, "single"),
    "decode-multi-step-1-with-a-schema": (
        dict(decode_multi_step=1), GREEDY_SCHEMA, 0, "single",
    ),
    "decode-lookahead-1": (dict(decode_lookahead=1), PLAIN, 0, "pipelined"),
    "windows-in-flight-after-a-constrained-row-was-admitted": (
        {}, GREEDY_SCHEMA, 2, "drain",
    ),
    "windows-in-flight-after-a-seeded-row-was-admitted": (
        {}, PLAIN._replace(has_row_seed=True), 1, "drain",
    ),
}


@pytest.mark.parametrize("case", sorted(CHOICES))
def test_the_choice_of_a_decode_path(case, byte_tok):
    engine_kw, facts, in_flight, want = CHOICES[case]
    assert _batcher(byte_tok, **engine_kw)._choose_path(
        facts, in_flight
    ) == want


def _rows(tok, schema=None, **kw):
    factory = schema_constraint_factory(schema, tok) if schema else None
    return [
        GenRequest(
            row_id=i, prompt_ids=np.array(tok.encode(t), np.int32),
            constraint=factory() if factory else None, **kw,
        )
        for i, t in enumerate(TEXTS)
    ]


# case -> (engine config, schema, request kwargs, the facts every
#          iteration must show, the plans allowed, the labels counted)
RUNS = {
    "sampled-and-plain": (
        {}, None, dict(max_new_tokens=24, temperature=0.7),
        dict(has_constraint=False, has_row_seed=False, has_penalty=False,
             all_greedy=False, flagged=False, wrapped=False),
        {"pipelined"}, {"pipelined"},
    ),
    "windows-in-flight-1": (
        dict(decode_lookahead=1), None,
        dict(max_new_tokens=24, temperature=0.7),
        dict(has_constraint=False), {"pipelined"}, {"pipelined"},
    ),
    "a-seeded-row": (
        {}, None, dict(max_new_tokens=12, temperature=0.7, row_seed=11),
        dict(has_row_seed=True, has_constraint=False),
        {"single"}, {"single"},
    ),
    "a-penalised-row": (
        {}, None,
        dict(max_new_tokens=12, temperature=0.0, presence_penalty=0.5),
        dict(has_penalty=True, all_greedy=True), {"single"}, {"single"},
    ),
    "constrained-sampled": (
        {}, ENUMS, dict(max_new_tokens=64, temperature=0.7),
        dict(has_constraint=True, constrained_greedy=False),
        {"single"}, {"single"},
    ),
    "constrained-greedy": (
        {}, ENUMS, dict(max_new_tokens=64, temperature=0.0),
        dict(has_constraint=True, constrained_greedy=True, all_greedy=True),
        {"fastforward"}, {"fastforward", "window"},
    ),
    # 5 pages of 8 a row: the tail of every row has room for less than
    # a window of 8, and takes single steps
    "room-runs-out": (
        dict(max_pages_per_seq=5, max_model_len=40), None,
        dict(max_new_tokens=40, temperature=0.7),
        dict(has_constraint=False), {"pipelined", "single"},
        {"pipelined", "single"},
    ),
}


@pytest.mark.parametrize("case", sorted(RUNS))
def test_the_facts_of_real_rows_and_the_path_they_take(
    case, byte_tok, monkeypatch
):
    engine_kw, schema, req_kw, facts, plans, labels = RUNS[case]
    telemetry.reset_for_tests()
    b = _batcher(byte_tok, **engine_kw)
    seen = []
    choose = ContinuousBatcher._choose_path

    def recording(self, f, in_flight):
        plan = choose(self, f, in_flight)
        seen.append((f, in_flight, plan))
        return plan

    monkeypatch.setattr(ContinuousBatcher, "_choose_path", recording)
    done = {}
    assert b.run(
        _rows(byte_tok, schema, **req_kw),
        on_result=lambda r: done.__setitem__(r.row_id, r),
    ) == "completed"
    assert len(done) == len(TEXTS) and seen
    for f, in_flight, plan in seen:
        assert {k: getattr(f, k) for k in facts} == facts
        assert 0 <= in_flight < max(b.ecfg.decode_lookahead, 1)
        # the least room of a row, in steps, never negative
        assert 0 <= f.room <= b.MP * b.ecfg.kv_page_size
    assert {plan for _f, _n, plan in seen} == plans
    if "single" in plans and "pipelined" in plans:
        KS = b.ecfg.decode_multi_step
        assert all(
            (f.room < KS) == (plan == "single") for f, _n, plan in seen
        )
    series = telemetry.REGISTRY.collect()[
        "sutro_sched_iterations_total"]["series"]
    assert {k for k, v in series.items() if v and k != "idle"} == labels
    assert sum(series.values()) >= len(seen)


@pytest.mark.parametrize("name", ["spec_ngram_draft", "no_such_field"])
@pytest.mark.parametrize("source", ["keyword", "engine.json"])
def test_a_field_the_engine_does_not_have(name, source, tmp_path, monkeypatch):
    """A configuration that names ``spec_ngram_draft`` (n-gram
    speculation, gone with PR 30) gets what any unknown field gets:
    ``load_engine_config`` leaves it out, ``EngineConfig`` refuses it."""
    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    if source == "keyword":
        ecfg = load_engine_config(decode_multi_step=4, **{name: 6})
    else:
        (tmp_path / "engine.json").write_text(
            json.dumps({"decode_multi_step": 4, name: 6})
        )
        ecfg = load_engine_config()
    assert ecfg.decode_multi_step == 4 and not hasattr(ecfg, name)
    with pytest.raises(TypeError, match=name):
        EngineConfig(**{name: 6})
