"""``sutro_sample_dispatches_total{head}`` (OBSERVABILITY.md "Parts of a
step"): every dispatch that samples counts once, by the side of
``ops.sampling.sample``'s cond the device takes for its batch: ``argmax``
when every row is at temperature 0, ``drawn`` when some row draws. The
count is the host's own reading of the temperatures the program is given;
that the device's predicate is the same one is ``tests/test_sampling.py``'s
to hold."""

import numpy as np
import pytest

from sutro_tpu import telemetry
from sutro_tpu.engine import scheduler as sched_mod
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.constrain import schema_constraint_factory
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest, JobCtx
from sutro_tpu.models.configs import MODEL_CONFIGS

SCHEMA = {
    "type": "object",
    "properties": {
        "label": {"type": "string", "enum": ["positive", "negative"]},
        "note": {"type": "string", "maxLength": 12},
    },
    "required": ["label", "note"],
}
HEADS = "sutro_sample_dispatches_total"
PATHS = "sutro_sched_iterations_total"
SITES = ("decode_step", "decode_multi_async", "decode_window")


@pytest.fixture(autouse=True)
def counting(monkeypatch):
    monkeypatch.setattr(telemetry, "ENABLED", True)


def _gained(before, after, name):
    """{series: n} of what counter ``name`` gained between two collects
    of the registry."""
    a, z = ((snap.get(name) or {}).get("series", {}) for snap in (before, after))
    return {k: int(z[k] - a.get(k, 0)) for k in z if z[k] != a.get(k, 0)}


def _run(tok, monkeypatch, *, temperature, constrained, hold_on_steps=False):
    """One job on a fresh batcher: what the counter gained by ``head``,
    the calls of each sampling dispatch, the scheduler's iterations by
    path, and the results."""
    calls = {site: 0 for site in SITES + ("admit",)}

    def counted(site, fn):
        def call(*a, **kw):
            calls[site] += 1
            return fn(*a, **kw)
        return call

    for site in SITES:
        monkeypatch.setattr(
            ModelRunner, site, counted(site, getattr(ModelRunner, site))
        )
    monkeypatch.setattr(
        sched_mod, "_admit_sample_jit",
        counted("admit", sched_mod._admit_sample_jit),
    )
    if hold_on_steps:
        # the masked single step whatever these weights' windows verify
        monkeypatch.setattr(sched_mod, "_window_gain", lambda p, K, c: 0.0)
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=32, max_model_len=256,
        decode_batch_size=4, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", decode_multi_step=8,
        decode_lookahead=2, constrain_fastforward=16,
    )
    b = ContinuousBatcher(
        ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg),
        stop_ids=tok.stop_ids(), token_bytes=tok.token_bytes, seed=5,
    )
    factory = schema_constraint_factory(SCHEMA, tok) if constrained else None
    rows = [
        GenRequest(
            row_id=i, prompt_ids=np.array(tok.encode(t), np.int32),
            max_new_tokens=60, temperature=temperature,
            constraint=factory() if constrained else None,
        )
        for i, t in enumerate(["first row", "second", "the third one"])
    ]
    before = telemetry.REGISTRY.collect()
    res = {}
    ctx = JobCtx(
        job_id=f"sample-heads-{temperature}-{constrained}", pending=rows,
        on_result=lambda r: res.__setitem__(r.row_id, r),
    )
    assert b.run_multi([ctx], on_job_done=lambda c, o: None) == "completed"
    after = telemetry.REGISTRY.collect()
    return (
        _gained(before, after, HEADS), calls, _gained(before, after, PATHS),
        res,
    )


def test_a_greedy_constrained_job_counts_argmax(byte_tok, monkeypatch):
    gained, calls, took, res = _run(
        byte_tok, monkeypatch, temperature=0.0, constrained=True,
        hold_on_steps=True,
    )
    assert len(res) == 3
    # masked steps and admission samples, and every one of them counted
    assert took.get("single", 0) > 0 and calls["decode_step"] == took["single"]
    assert calls["admit"] > 0
    assert gained == {"argmax": sum(calls.values())}, (gained, calls, took)


@pytest.mark.parametrize("constrained", [False, True], ids=["plain", "schema"])
def test_a_job_at_temperature_seven_tenths_counts_drawn(
    constrained, byte_tok, monkeypatch
):
    gained, calls, took, res = _run(
        byte_tok, monkeypatch, temperature=0.7, constrained=constrained,
    )
    assert len(res) == 3
    assert calls["admit"] > 0
    # plain rows ride fused windows, sampled constrained rows masked steps
    site = "decode_step" if constrained else "decode_multi_async"
    assert calls[site] > 0, calls
    assert gained == {"drawn": sum(calls.values())}, (gained, calls, took)


def test_a_greedy_window_counts_argmax(byte_tok, monkeypatch):
    """Plain greedy rows: the fused window's sampler takes the argmax
    side in every step of every window."""
    gained, calls, _, _ = _run(
        byte_tok, monkeypatch, temperature=0.0, constrained=False,
    )
    assert calls["decode_multi_async"] > 0
    assert gained == {"argmax": sum(calls.values())}


def test_the_predicate_is_the_samplers_own():
    """One drawing row among greedy ones is ``drawn``; padding rows (an
    admission bucket's, at temperature 0) do not make a batch greedy."""
    for temps, head in (
        (np.zeros(4, np.float32), "argmax"),
        (np.asarray([0.0, 0.0, 0.7, 0.0], np.float32), "drawn"),
        (np.asarray([-1.0, 0.0], np.float32), "argmax"),
        (np.full(4, 0.7, np.float32), "drawn"),
    ):
        before = telemetry.REGISTRY.collect()
        ModelRunner.count_sample(temps)
        assert _gained(before, telemetry.REGISTRY.collect(), HEADS) == {head: 1}
