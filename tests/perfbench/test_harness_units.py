"""The harness's own arithmetic: pools that every seed shares, the
window's rate, the rules of ``correct`` for chats, the schema check."""

import numpy as np
import pytest

from perfbench import correctness, schema_check, stats
from perfbench.clientlog import ClientLog
from perfbench.generators import batch_jobs, chat_open_loop
from perfbench.generators.textgen import text_of_length


class FakeSut:
    model = "m"

    def decode_batch(self):
        return 8


class FakeEnv:
    def __init__(self, seed, seconds=10.0):
        self.seed, self.seconds, self.sut = seed, seconds, FakeSut()
        self.log = ClientLog()

    def rng(self, name):
        import zlib

        return np.random.default_rng([self.seed, zlib.crc32(name.encode())])


BATCH = {
    "generator": "batch_jobs", "clients": 2, "rows_per_job": {"of_decode_batch": 0.5},
    "max_new_tokens_cycle": [4], "lead_in_s": 0,
    "prompt_chars": {"pool": 64, "pool_seed": 9, "median": 100, "sigma": 0.5,
                     "min": 20, "max": 300, "long_every": 8, "long_min": 400,
                     "long_max": 500},
}
CHAT = {
    "generator": "chat_open_loop", "rate_per_s": 5.0, "arrivals": {"pool_seed": 3},
    "system_prompt_chars": 64, "max_tokens_choices": [4, 8], "lead_in_s": 2.0,
    "user_chars": {"pool": 32, "pool_seed": 4, "median": 50, "sigma": 0.5,
                   "min": 10, "max": 120},
}


def test_percentile_interpolates():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    assert stats.median([1, 2, 3, 4]) == pytest.approx(2.5)


def test_text_has_the_exact_length():
    rng = np.random.default_rng(0)
    for n in (8, 48, 333):
        assert len(text_of_length(rng, n, head="Review 1:")) == n


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_every_seed_gets_the_same_prompt_sizes(seed):
    a = batch_jobs.build(BATCH, FakeEnv(0))
    b = batch_jobs.build(BATCH, FakeEnv(seed))
    assert a.rows_per_job == 4
    assert sum(1 for n in a.pool if n >= 400) == 8      # every eighth is long
    # every job of every seed holds the same sizes, in another order
    ja, jb, jb2 = a._next_lengths(64), b._next_lengths(64), b._next_lengths(64)
    assert sorted(ja) == sorted(jb) == sorted(jb2) == sorted(a.pool)
    assert ja != jb and jb != jb2
    assert sorted(a._next_lengths(128)) == sorted(a.pool * 2)


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_every_seed_offers_the_same_arrivals_in_another_order(seed):
    a = chat_open_loop.build(CHAT, FakeEnv(0))
    b = chat_open_loop.build(CHAT, FakeEnv(seed))
    assert sorted(a.user_chars) == sorted(b.user_chars)
    # about rate x (lead-in + window) requests, some before the window
    for g in (a, b):
        assert 40 <= len(g.offsets) <= 80 and g.offsets[0] < 0 < g.offsets[-1] < 10
        assert g.offsets == sorted(g.offsets)
    assert a.offsets != b.offsets


def test_window_rate_is_between_first_and_last_update():
    log = ClientLog()
    for t, out in ((9.0, 50), (10.5, 100), (11.5, 200), (12.5, 300), (14.0, 400)):
        log.tokens(t, "job-a", out, 0)
    log.tokens(11.0, "job-b", 40, 0)
    log.tokens(12.0, "job-b", 90, 0)
    for t in (10.0, 11.0, 12.0, 12.4, 13.0):
        log.chat_token(t)
    first, last, tokens = log.window_rate_points(10.0, 13.0)
    # job-a 100->300, job-b 0->90 (its first update is inside), chats
    # in (10.5, 12.5]: three
    assert (first, last, tokens) == (10.5, 12.5, 200 + 90 + 3)
    assert log.window_rate_points(20.0, 30.0) is None


def chat(**kw):
    base = {"trace_id": "t", "done": 1.0, "error": None, "finish_reason": "length",
            "tokens": 8, "max_tokens": 8, "warm": False}
    return dict(base, **kw)


@pytest.mark.parametrize("rec,bad", [
    (chat(), False),
    (chat(finish_reason="stop", tokens=1), False),
    (chat(tokens=9), True),
    (chat(tokens=0), True),
    (chat(finish_reason="error_capacity"), True),
    (chat(error="Refused: 503", done=None), False),    # failed, not wrong
    (chat(done=None), False),                          # unfinished: nothing to judge
])
def test_chat_accounting(rec, bad):
    log = ClientLog()
    log.add_chat(rec)
    problems, _facts = correctness.accounting(log)
    assert bool(problems) == bad


def test_job_rows_accounting_and_schemas():
    schema = {"type": "object", "required": ["label"],
              "properties": {"label": {"type": "string", "enum": ["yes", "no"]}}}
    gen = object.__new__(batch_jobs.BatchJobs)
    rec = {"job_id": "j", "rows": 5, "max_new_tokens": 10, "schema": schema,
           "problems": [], "length_rows": 0}
    rows = [
        {"row_id": 0, "output": '{"label": "yes"}', "finish_reason": "schema_complete", "gen_tokens": 9, "error": None},
        {"row_id": 1, "output": '{"label": "ye', "finish_reason": "length", "gen_tokens": 10, "error": None},
        {"row_id": 2, "output": '{"label": "maybe"}', "finish_reason": "schema_complete", "gen_tokens": 9, "error": None},
        {"row_id": 3, "output": None, "finish_reason": "error", "gen_tokens": 0, "error": "boom"},
        {"row_id": 4, "output": "", "finish_reason": "stop", "gen_tokens": 0, "error": None},
    ]
    gen._check_rows(rec, rows)
    assert rec["length_rows"] == 2          # rows 1 and 4: counted, not parsed
    assert len(rec["problems"]) == 2
    assert "row 2" in rec["problems"][0] and "row 3" in rec["problems"][1]


@pytest.mark.parametrize("value,ok", [
    ({"scratchpad": "x" * 400, "classification": "neutral"}, True),
    ({"scratchpad": "x" * 401, "classification": "neutral"}, False),
    ({"scratchpad": "", "classification": "mixed"}, False),
    ({"classification": "positive"}, False),
    ("not an object", False),
])
def test_schema_check_on_the_classification_schema(value, ok):
    import json
    from pathlib import Path

    traffic = json.loads(
        (Path(schema_check.__file__).parent / "traffic/classify-jobs.json").read_text()
    )
    assert (schema_check.violation(value, traffic["output_schema"]) is None) == ok
