"""The scheduler chooses a decode path in one place
(``ContinuousBatcher._choose_path``): a table of facts -> path with one
case a gate (the share of unmasked tokens the FSMs accept, on both
sides of the line and inside the band where a batch stays where it is,
among them), the facts ``_build_batch`` reads off real rows and the
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
    _window_gain,
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
# the same batch once its fast-forward probe has disengaged, by the
# share of its unmasked tokens the FSMs accept: the line lies at ~0.89
# for a window of 8, the band where a batch stays where it is ~0.86-0.92
PROBED = dict(probed=True)


def _accepting(share, stepping=False, **facts):
    return GREEDY_SCHEMA._replace(
        unmasked_ok=share, stepping=stepping, **facts
    )


# case -> (engine config, facts, windows in flight, the path to try); an
#         engine config may carry ``probed``, _choose_path's own argument
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
    # window or masked step, from the share of unmasked tokens accepted
    "probed-before-any-observation": (PROBED, GREEDY_SCHEMA, 0, "window"),
    "probed-every-unmasked-token-accepted": (
        PROBED, _accepting(1.0), 0, "window",
    ),
    "probed-no-unmasked-token-accepted": (
        PROBED, _accepting(0.0), 0, "single",
    ),
    "probed-well-above-the-line": (PROBED, _accepting(0.95), 0, "window"),
    "probed-well-below-the-line": (PROBED, _accepting(0.6), 0, "single"),
    "probed-above-the-line-after-steps": (
        PROBED, _accepting(0.95, stepping=True), 0, "window",
    ),
    "probed-below-the-line-after-steps": (
        PROBED, _accepting(0.6, stepping=True), 0, "single",
    ),
    # inside the band a batch stays where it is
    "probed-just-below-the-line-on-windows": (
        PROBED, _accepting(0.865), 0, "window",
    ),
    "probed-just-above-the-line-on-steps": (
        PROBED, _accepting(0.895, stepping=True), 0, "single",
    ),
    "probed-just-below-the-line-on-steps": (
        PROBED, _accepting(0.865, stepping=True), 0, "single",
    ),
    "probed-just-above-the-line-on-windows": (
        PROBED, _accepting(0.895), 0, "window",
    ),
    # the band's edges as the constants before PR 53 put them (1.97 and
    # 0.68 kept a batch on windows at 0.79 and sent one back to them at
    # 0.87)
    "probed-at-the-band's-lower-edge-on-windows": (
        PROBED, _accepting(0.79), 0, "single",
    ),
    "probed-at-the-band's-upper-edge-on-steps": (
        PROBED, _accepting(0.87, stepping=True), 0, "single",
    ),
    "probed-under-the-band-on-windows": (
        PROBED, _accepting(0.83), 0, "single",
    ),
    "probed-over-the-band-on-steps": (
        PROBED, _accepting(0.93, stepping=True), 0, "window",
    ),
    # a window keeps a plain row's every token: one refused row among
    # three plain ones does not take the batch off windows, refused rows
    # among as many plain ones do
    "probed-one-refused-row-in-four": (
        PROBED, _accepting(0.0, constrained=0.25), 0, "window",
    ),
    "probed-refused-rows-half-the-batch": (
        PROBED, _accepting(0.0, constrained=0.5), 0, "single",
    ),
    "probed-refused-rows-three-in-four": (
        PROBED, _accepting(0.0, constrained=0.75), 0, "single",
    ),
    "one-refused-row-in-four-beside-sampled-plain-rows": (
        {}, _accepting(0.0, constrained=0.25, all_greedy=False), 0, "window",
    ),
    # the probe stays ahead of both choices
    "unprobed-no-unmasked-token-accepted": (
        {}, _accepting(0.0, stepping=True), 0, "fastforward",
    ),
    # no verify forward there: the same rule, window against step
    "sp-above-one-no-unmasked-token-accepted": (
        {}, _accepting(0.0, wrapped=True), 0, "single",
    ),
    "beside-sampled-plain-rows-no-unmasked-token-accepted": (
        {}, _accepting(0.0, all_greedy=False), 0, "single",
    ),
    # whoever chose as before chooses as before
    "constrained-sampled-every-unmasked-token-accepted": (
        {},
        PLAIN._replace(has_constraint=True, constrained_greedy=False,
                       unmasked_ok=1.0, stepping=True),
        0, "single",
    ),
    "room-under-one-window-every-unmasked-token-accepted": (
        PROBED, _accepting(1.0, room=7), 0, "single",
    ),
    "a-seeded-row-every-unmasked-token-accepted": (
        PROBED, _accepting(1.0, has_row_seed=True), 0, "single",
    ),
    "a-flagged-row-no-unmasked-token-accepted": (
        PROBED, _accepting(0.0, flagged=True), 0, "single",
    ),
    "plain-rows-no-unmasked-token-accepted": (
        {}, PLAIN._replace(unmasked_ok=0.0, stepping=True), 0, "pipelined",
    ),
}


@pytest.mark.parametrize("case", sorted(CHOICES))
def test_the_choice_of_a_decode_path(case, byte_tok):
    engine_kw, facts, in_flight, want = CHOICES[case]
    engine_kw = dict(engine_kw)
    probed = engine_kw.pop("probed", False)
    assert _batcher(byte_tok, **engine_kw)._choose_path(
        facts, in_flight, probed
    ) == want


@pytest.mark.parametrize("K", [2, 4, 8, 16])
def test_what_a_window_is_worth_in_masked_steps(K):
    """Tokens a second of a window of K over a masked step's: under 1
    where nothing verifies (K steps of the device for the one masked
    token), over 1 where everything does (the host's rounds saved),
    rising between, K times from end to end."""
    gains = [_window_gain(p / 100.0, K) for p in range(0, 101)]
    assert gains[0] < 1.0 < gains[-1]
    assert all(a < b for a, b in zip(gains, gains[1:]))
    assert gains[-1] / gains[0] == pytest.approx(K)
    # a plain row is worth a window's whole width whatever the FSMs say
    assert _window_gain(0.0, K, 0.0) == pytest.approx(gains[-1])
    assert gains[0] < _window_gain(0.0, K, 0.5) < gains[-1]


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
    # random weights under a byte tokenizer: the probe is asked first
    # every iteration; its first verify forward finds none of the rows'
    # unmasked tokens valid along the scaffold, so where it disengages
    # the batch takes masked steps, and no window at all
    # (tests/test_decode_yield.py rigs both sides of the line)
    "constrained-greedy": (
        {}, ENUMS, dict(max_new_tokens=64, temperature=0.0),
        dict(has_constraint=True, constrained_greedy=True, all_greedy=True),
        {"fastforward", "single"}, {"fastforward", "single"},
    ),
    # no verify forward to learn from: one window, refused, then steps
    "constrained-greedy-fastforward-off": (
        dict(constrain_fastforward=0), ENUMS,
        dict(max_new_tokens=64, temperature=0.0),
        dict(has_constraint=True, constrained_greedy=True, all_greedy=True),
        {"fastforward", "window", "single"}, {"window", "single"},
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
    # as a new session starts: nothing observed yet
    b._unmasked_ok, b._stepping = 1.0, False
    seen = []
    asked_again = []
    choose = ContinuousBatcher._choose_path

    def recording(self, f, in_flight, probed=False):
        plan = choose(self, f, in_flight, probed)
        seen.append((f, in_flight, plan))
        if probed:
            # the same iteration, after its probe disengaged
            assert seen[-2] == (f, in_flight, "fastforward")
            assert plan in ("window", "single")
            asked_again.append(plan)
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
    assert sum(series.values()) >= len(seen) - len(asked_again)
    assert ("fastforward" in plans) == bool(asked_again)


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
