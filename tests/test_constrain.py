"""Constrained decoding: schema compiler, NFA semantics, token masks,
C++/Python parity."""

import json

import numpy as np
import pytest
from pydantic import BaseModel

from sutro_tpu.common import normalize_output_schema
from sutro_tpu.engine.constrain import (
    TokenTable,
    compile_schema,
    schema_constraint_factory,
)
from sutro_tpu.engine.constrain.fsm import MaskCache
from sutro_tpu.engine.tokenizer import ByteTokenizer


def accepts(nfa, text: str) -> bool:
    states = nfa.initial()
    for b in text.encode():
        states = nfa.step(states, b)
        if not states:
            return False
    return nfa.is_accepting(states)


@pytest.mark.parametrize(
    "schema,good,bad",
    [
        (
            {"type": "object", "properties": {"x": {"type": "integer"}},
             "required": ["x"]},
            ['{"x":0}', '{"x":-17}', '{"x":123456}'],
            ['{"x":01}', '{"x":1.5}', '{}', '{"x": 1}', '{"y":1}'],
        ),
        (
            {"type": "object", "properties": {"s": {"type": "string"}},
             "required": ["s"]},
            ['{"s":""}', '{"s":"hi"}', '{"s":"q\\"uote"}', '{"s":"\\u00e9"}'],
            ['{"s":5}', '{"s":"unterminated}', '{"s":"bad\\q"}'],
        ),
        (
            {"type": "object",
             "properties": {"t": {"type": "array", "items": {"type": "boolean"}}},
             "required": ["t"]},
            ['{"t":[]}', '{"t":[true]}', '{"t":[true,false,true]}'],
            ['{"t":[true,]}', '{"t":[1]}', '{"t":'],
        ),
        (
            {"type": "object",
             "properties": {
                 "a": {"type": "number"},
                 "b": {"enum": ["x", "y"]},
             },
             "required": ["b"]},
            ['{"a":1.5,"b":"x"}', '{"b":"y"}', '{"a":-2e3,"b":"x"}'],
            ['{"b":"z"}', '{"a":1.5}', '{"b":"x","a":1}'],  # fixed key order
        ),
    ],
)
def test_schema_acceptance(schema, good, bad):
    nfa = compile_schema(schema)
    for g in good:
        json.loads(g)  # sanity: must be valid JSON
        assert accepts(nfa, g), f"should accept {g}"
    for bstr in bad:
        assert not accepts(nfa, bstr), f"should reject {bstr}"


def test_pydantic_schema_with_enum_and_optional():
    from enum import Enum

    class Color(str, Enum):
        red = "red"
        blue = "blue"

    class M(BaseModel):
        color: Color
        note: str = "d"  # optional (has default => not required)

    nfa = compile_schema(normalize_output_schema(M))
    assert accepts(nfa, '{"color":"red","note":"hi"}')
    assert accepts(nfa, '{"color":"blue"}')
    assert not accepts(nfa, '{"color":"green"}')


def test_nested_object_and_anyof():
    schema = {
        "type": "object",
        "properties": {
            "sub": {
                "type": "object",
                "properties": {"x": {"type": "integer"}},
                "required": ["x"],
            },
            "opt": {"anyOf": [{"type": "integer"}, {"type": "null"}]},
        },
        "required": ["sub"],
    }
    nfa = compile_schema(schema)
    assert accepts(nfa, '{"sub":{"x":1}}')
    assert accepts(nfa, '{"sub":{"x":1},"opt":null}')
    assert accepts(nfa, '{"sub":{"x":1},"opt":42}')
    assert not accepts(nfa, '{"sub":{},"opt":null}')


def test_string_length_bounds():
    schema = {
        "type": "object",
        "properties": {"s": {"type": "string", "minLength": 2, "maxLength": 4}},
        "required": ["s"],
    }
    nfa = compile_schema(schema)
    assert not accepts(nfa, '{"s":"a"}')
    assert accepts(nfa, '{"s":"ab"}')
    assert accepts(nfa, '{"s":"abcd"}')
    assert not accepts(nfa, '{"s":"abcde"}')


def test_token_fsm_forces_valid_json():
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {"k": {"enum": ["a", "b"]}},
        "required": ["k"],
    }
    fac = schema_constraint_factory(schema, tok)
    fsm = fac()
    # walk by always taking the lexicographically-smallest allowed token
    out = bytearray()
    for _ in range(64):
        if fsm.is_complete():
            break
        mask = fsm.allowed_tokens()
        tid = int(np.argmax(mask))
        fsm.advance(tid)
        out += tok.token_bytes(tid)
        if fsm.is_complete():
            break
    parsed = json.loads(out.decode())
    assert parsed["k"] in ("a", "b")


def test_mask_allows_stop_only_at_accept():
    tok = ByteTokenizer()
    schema = {"type": "object", "properties": {"n": {"type": "integer"}},
              "required": ["n"]}
    fac = schema_constraint_factory(schema, tok)
    fsm = fac()
    assert not fsm.allowed_tokens()[tok.eos_id]
    for ch in b'{"n":7':
        fsm.advance(ch)
    # '7' could continue (more digits) or close; eos not yet allowed
    assert not fsm.allowed_tokens()[tok.eos_id]
    fsm.advance(ord("}"))
    assert fsm.is_complete()
    assert fsm.allowed_tokens()[tok.eos_id]


def _assert_cpp_py_parity(schema, text: str, expect_accept=False):
    """Walk ``text`` byte-wise asserting the C++ and Python maskers
    agree at every state (shared harness for every parity case)."""
    pytest.importorskip("ctypes")
    from sutro_tpu.engine.constrain.cpp import CppMasker

    tok = ByteTokenizer()
    nfa = compile_schema(schema)
    table = TokenTable(tok)
    try:
        cpp = CppMasker(nfa, table)
    except Exception:
        pytest.skip("native toolchain unavailable")
    py = MaskCache(nfa, table)
    py._cpp = None
    states = nfa.initial()
    for ch in text.encode():
        pm, pd = py._compute(states)
        cm, cd = cpp.mask(states)
        np.testing.assert_array_equal(pm, cm)
        np.testing.assert_array_equal(pd, cd)
        states = nfa.step(states, ch)
        assert states, chr(ch)
    if expect_accept:
        assert nfa.is_accepting(states)


def test_cpp_python_mask_parity():
    _assert_cpp_py_parity(
        {
            "type": "object",
            "properties": {
                "s": {"type": "string"},
                "v": {"type": "number"},
                "e": {"enum": ["aa", "ab", "b"]},
            },
            "required": ["s", "v", "e"],
        },
        '{"s":"x\\n","v":-1.5e2,"e":"ab"}',
    )


def test_budget_aware_closure_always_completes():
    """With a token budget too small for free-running string content, the
    FSM must steer to closing bytes so the emitted JSON is complete
    (verify-session regression: mid-string cuts at the length cap)."""
    import json

    from sutro_tpu.engine.constrain.fsm import schema_constraint_factory

    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {"label": {"type": "string"}},
        "required": ["label"],
    }
    nested = {
        "type": "array",
        "items": {
            "type": "object",
            "properties": {"label": {"type": "string"}},
            "required": ["label"],
        },
    }
    rng = np.random.default_rng(0)
    for sch, check in (
        (schema, lambda o: "label" in o),
        (nested, lambda o: isinstance(o, list)),
    ):
        factory = schema_constraint_factory(sch, tok)
        for budget in (14, 20, 40):
            fsm = factory()
            out = bytearray()
            remaining = budget
            while remaining > 0 and not fsm.is_complete():
                mask = fsm.allowed_tokens(remaining=remaining)
                ids = np.nonzero(mask)[0]
                assert len(ids), "mask must never be empty"
                # adversarial: pick a random allowed token (worst-case model)
                tid = int(rng.choice(ids))
                fsm.advance(tid)
                out.extend(tok.token_bytes(tid))
                remaining -= 1
            obj = json.loads(out.decode("utf-8", errors="strict"))
            assert check(obj), (sch, budget, out)


def test_distance_to_accept():
    from sutro_tpu.engine.constrain.schema import compile_schema as cs

    nfa = cs({"enum": ["ab"]})  # JSON: "ab" -> 4 bytes: " a b "
    d0 = nfa.dist_to_accept(nfa.initial())
    assert d0 == 4


def test_schema_min_tokens_raises_generation_cap(tiny_ecfg, tmp_path, monkeypatch):
    """A max_new_tokens below the schema's shortest accepting output must
    not break the schema guarantee: the engine raises the row cap to the
    FSM's min_tokens so constrained rows still emit complete JSON."""
    import dataclasses
    import json
    import time

    monkeypatch.setenv("SUTRO_HOME", str(tmp_path))
    from sutro_tpu.engine.api import LocalEngine
    from sutro_tpu.interfaces import JobStatus

    ecfg = dataclasses.replace(
        tiny_ecfg, max_pages_per_seq=32, max_model_len=256
    )
    eng = LocalEngine(ecfg)
    jid = eng.submit_batch_inference(
        {
            "model": "tiny-dense",
            "inputs": ["x"],
            "sampling_params": {"max_new_tokens": 4},  # << schema minimum
            "output_schema": {
                "type": "object",
                "properties": {
                    "label": {"type": "string", "enum": ["aa", "bb"]}
                },
                "required": ["label"],
            },
        }
    )
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if JobStatus(eng.job_status(jid)).is_terminal():
            break
        time.sleep(0.05)
    assert eng.job_status(jid) == "SUCCEEDED"
    out = eng.job_results(jid)["outputs"][0]
    parsed = json.loads(out)  # complete JSON despite the 4-token cap
    assert parsed["label"] in ("aa", "bb")


def test_speculative_constrained_matches_masked(tiny_ecfg, byte_tok):
    """Greedy schema-constrained generation must produce IDENTICAL
    outputs whether every step is masked (decode_multi_step=1) or fused
    speculative windows verify-and-commit (decode_multi_step=8): for
    greedy rows, the unmasked argmax is accepted only when it equals the
    masked argmax, and rejections fall back to one masked step."""
    import dataclasses
    import json

    from sutro_tpu.engine.constrain import schema_constraint_factory
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest
    from sutro_tpu.models.configs import MODEL_CONFIGS

    schema = {
        "type": "object",
        "properties": {
            "note": {"type": "string", "maxLength": 20},
            "label": {"type": "string", "enum": ["alpha", "beta"]},
        },
        "required": ["note", "label"],
    }

    def run(multi):
        ecfg = dataclasses.replace(
            tiny_ecfg, decode_multi_step=multi, max_pages_per_seq=32,
            max_model_len=256,
        )
        runner = ModelRunner(MODEL_CONFIGS["tiny-dense"], ecfg)
        factory = schema_constraint_factory(schema, byte_tok)
        reqs = [
            GenRequest(
                row_id=i,
                prompt_ids=np.array(byte_tok.encode(t), np.int32),
                max_new_tokens=80,
                temperature=0.0,
                constraint=factory(),
            )
            for i, t in enumerate(["first row", "second", "third one"])
        ]
        b = ContinuousBatcher(runner, stop_ids=byte_tok.stop_ids())
        res = {}
        b.run(reqs, on_result=lambda r: res.__setitem__(r.row_id, r))
        return {
            i: (tuple(r.token_ids), r.finish_reason)
            for i, r in res.items()
        }

    masked = run(1)
    spec = run(8)
    assert masked == spec
    # and every output is complete, schema-valid JSON
    for toks, _reason in masked.values():
        parsed = json.loads(byte_tok.decode(list(toks)))
        assert parsed["label"] in ("alpha", "beta")


# ---------------------------------------------------------------------------
# Integer minimum/maximum (interval automaton) + string pattern (regex)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "lo,hi",
    [(0, 10), (1, 5), (7, 7), (-5, 5), (-30, -7), (17, 40163), (None, 12),
     (3, None), (None, -4), (-9, None), (0, None), (None, 0)],
)
def test_integer_bounds_exact(lo, hi):
    """The digit-interval automaton accepts exactly the integers in
    range — brute-force checked against int comparison."""
    schema = {"type": "integer"}
    if lo is not None:
        schema["minimum"] = lo
    if hi is not None:
        schema["maximum"] = hi
    nfa = compile_schema(schema)
    for v in list(range(-60, 61)) + [1234, -1234, 40162, 40163, 40164, 99999]:
        want = (lo is None or v >= lo) and (hi is None or v <= hi)
        assert accepts(nfa, str(v)) == want, (v, lo, hi)
    # canonical form only: no leading zeros / plus signs ever
    assert not accepts(nfa, "007")
    assert not accepts(nfa, "+3")


def test_integer_exclusive_bounds():
    nfa = compile_schema(
        {"type": "integer", "exclusiveMinimum": 2, "exclusiveMaximum": 6}
    )
    for v in range(-3, 10):
        assert accepts(nfa, str(v)) == (3 <= v <= 5), v


@pytest.mark.parametrize(
    "pattern,good,bad",
    [
        (r"^[a-z]+$", ["abc", "z"], ["", "Abc", "ab1"]),
        (r"^\d{3}-\d{4}$", ["555-0199"], ["5550199", "55-0199", "555-019"]),
        (r"^(yes|no)$", ["yes", "no"], ["maybe", "yesno", ""]),
        # unanchored (JSON Schema semantics): substring match
        (r"cat", ["cat", "concatenate", "cat!"], ["dog", "ca t"]),
        (r"^[A-Z][a-z]*( [A-Z][a-z]*)*$", ["Hello World", "A"], ["hello", "A  B"]),
        (r"^v\d+\.\d+\.\d+$", ["v1.20.3"], ["v1.2", "1.2.3"]),
        (r"^[^0-9]*$", ["abc", ""], ["a1"]),
        (r"^a{2,4}$", ["aa", "aaaa"], ["a", "aaaaa"]),
        # class escapes: known literals map, punctuation stays literal
        (r"^[a\-z]+$", ["a", "-", "z", "a-z"], ["b", "m"]),
        (r"^[\t]$", ["\t"], [" ", "t"]),
        # escaped range-high endpoint maps (\t-\n = 0x09-0x0A; wider
        # ranges through 0x0B fall back — \v has no JSON short escape)
        (r"^[\t-\n]$", ["\t", "\n"], [" ", "t", "n", "\r"]),
    ],
)
def test_string_pattern_enforced(pattern, good, bad):
    nfa = compile_schema(
        {
            "type": "object",
            "properties": {"s": {"type": "string", "pattern": pattern}},
            "required": ["s"],
        }
    )
    for s in good:
        assert accepts(nfa, json.dumps({"s": s}, separators=(",", ":"))), s
    for s in bad:
        assert not accepts(nfa, json.dumps({"s": s}, separators=(",", ":"))), s


def test_unsupported_pattern_falls_back_with_warning():
    """Exotic constructs keep the job alive: the string is type-checked
    but the pattern is not enforced (documented fallback)."""
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        nfa = compile_schema(
            {"type": "string", "pattern": r"^(?=lookahead)x$"}
        )
        assert any("not enforced" in str(x.message) for x in w)
    assert accepts(nfa, '"anything"')


@pytest.mark.parametrize(
    "pattern",
    [
        r"^[\x41]$",        # hex escape in class (would wrongly match "x"/"4"/"1")
        r"^[\x20-\x7E]+$",  # printable-ASCII idiom — hex range
        r"^[a-\x]$",        # exotic escape as range-high endpoint
        "^[\\u0041]$",      # unicode escape in class
        r"^[\1]$",          # backref-looking digit escape in class
    ],
)
def test_class_escape_exotic_falls_back(pattern):
    """Unrecognized escapes inside character classes must raise
    UnsupportedPattern (not silently degrade to the escape letter's
    literal — advisor round-2 medium), which routes the whole pattern
    into the documented warn-and-fallback path."""
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        nfa = compile_schema({"type": "string", "pattern": pattern})
        assert any("not enforced" in str(x.message) for x in w), pattern
    # fallback accepts any string — crucially "x" is no longer wrongly
    # privileged over "A" by a mis-compiled class
    assert accepts(nfa, '"A"')
    assert accepts(nfa, '"x"')


def test_pattern_masks_drive_valid_generation():
    """End-to-end with the token FSM: masked sampling over a byte
    vocabulary can only produce strings matching the pattern."""
    schema = {
        "type": "object",
        "properties": {"id": {"type": "string", "pattern": r"^[A-Z]{2}\d{2}$"}},
        "required": ["id"],
    }
    tok = ByteTokenizer()
    factory = schema_constraint_factory(schema, tok)
    fsm = factory()
    rng = np.random.default_rng(0)
    out = bytearray()
    for _ in range(64):
        if fsm.is_complete():
            break
        ids = np.flatnonzero(fsm.allowed_tokens())
        assert len(ids), "dead state"
        t = int(rng.choice(ids))
        fsm.advance(t)
        out += tok.token_bytes(t)
    obj = json.loads(out.decode())
    import re

    assert re.fullmatch(r"[A-Z]{2}\d{2}", obj["id"])


def test_integer_bounds_edge_semantics():
    """Fractional bounds round inward; draft-4 boolean and draft-2020
    numeric exclusive forms intersect with minimum/maximum."""
    # fractional: minimum 2.5 -> 3 is the smallest valid integer
    nfa = compile_schema({"type": "integer", "minimum": 2.5})
    assert not accepts(nfa, "2") and accepts(nfa, "3")
    nfa = compile_schema({"type": "integer", "maximum": -0.5})
    assert not accepts(nfa, "0") and accepts(nfa, "-1")
    # draft-2020: both keywords apply independently
    nfa = compile_schema(
        {"type": "integer", "minimum": 10, "exclusiveMinimum": 2}
    )
    assert not accepts(nfa, "3") and not accepts(nfa, "9")
    assert accepts(nfa, "10")
    # draft-4 boolean form
    nfa = compile_schema(
        {"type": "integer", "minimum": 10, "exclusiveMinimum": True,
         "maximum": 12}
    )
    assert not accepts(nfa, "10") and accepts(nfa, "11")
    # exclusiveMinimum -2.5: v > -2.5 -> -2 is valid
    nfa = compile_schema({"type": "integer", "exclusiveMinimum": -2.5})
    assert accepts(nfa, "-2") and not accepts(nfa, "-3")


def test_malformed_and_oversized_patterns_fall_back():
    """Malformed braces and unbounded repetition caps degrade to the
    unconstrained string (warning), never crash or blow up memory."""
    import warnings

    for pat in ["a{b}", "x{}", "a{2,x}", "^a{200000,}$", "a{-1}"]:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            nfa = compile_schema({"type": "string", "pattern": pat})
            assert any("not enforced" in str(x.message) for x in w), pat
        assert accepts(nfa, '"whatever"'), pat


@pytest.mark.parametrize(
    "fmt,good,bad",
    [
        ("uuid", ["123e4567-e89b-12d3-a456-426614174000"],
         ["123e4567e89b12d3a456426614174000", "123E4567-e89b-12d3-a456-426614174000", "xyz"]),
        ("date", ["2026-07-30", "1999-12-01"],
         ["2026-13-01", "2026-00-10", "2026-01-32", "26-07-30"]),
        ("date-time", ["2026-07-30T23:59:59Z", "2026-07-30T00:00:00+05:30",
                       "2026-07-30T12:00:00.123"],
         ["2026-07-30 12:00:00", "2026-07-30T24:00:00Z"]),
        ("time", ["23:59:59", "00:00:00Z", "12:30:45.5+05:30"],
         ["24:00:00", "12:60:00", "1:00:00", "12:00"]),
        ("email", ["a@b.co", "first.last+tag@example.org"],
         ["no-at-sign", "@x.com", "a@b", "a@b."]),
        ("ipv4", ["0.0.0.0", "255.255.255.255", "192.168.1.7"],
         ["256.1.1.1", "1.2.3", "01.2.3.4", "1.2.3.4.5"]),
    ],
)
def test_string_format_enforced(fmt, good, bad):
    nfa = compile_schema({"type": "string", "format": fmt})
    for s in good:
        assert accepts(nfa, json.dumps(s)), (fmt, s)
    for s in bad:
        assert not accepts(nfa, json.dumps(s)), (fmt, s)


def test_unknown_format_is_annotation_only():
    nfa = compile_schema({"type": "string", "format": "hostname"})
    assert accepts(nfa, '"anything at all"')


def test_format_with_length_bounds_defers_to_lengths():
    """minLength/maxLength are validator-enforced; format is annotation.
    When both appear the length bounds win, so generated values never
    fail the user's own validation."""
    nfa = compile_schema(
        {"type": "string", "format": "uuid", "maxLength": 10}
    )
    assert accepts(nfa, '"short"')          # within maxLength
    assert not accepts(nfa, '"12345678901"')  # 11 chars > maxLength


def test_unsupported_pattern_falls_back_to_format():
    """A pattern outside the regex subset degrades to the format grammar
    (closer than an unconstrained string) when one is available."""
    import warnings

    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        nfa = compile_schema(
            {"type": "string", "pattern": r"(?=x)a", "format": "ipv4"}
        )
    assert accepts(nfa, '"10.0.0.1"')
    assert not accepts(nfa, '"not an ip"')


@pytest.mark.parametrize(
    "lo,hi",
    [("0", "10"), ("1.5", "3.5"), ("0.25", "0.75"), ("2", "2"),
     ("-5.5", "5.5"), ("-30.2", "-7.85"), ("17", "40163.125"),
     (None, "12.5"), ("3.25", None), (None, "-4.5"), ("-0.5", None)],
)
def test_number_bounds_exact(lo, hi):
    """The decimal interval automaton accepts exactly the in-range
    plain decimals — brute-force checked against Decimal comparison."""
    import decimal

    schema = {"type": "number"}
    if lo is not None:
        schema["minimum"] = float(lo)
    if hi is not None:
        schema["maximum"] = float(hi)
    nfa = compile_schema(schema)
    dlo = None if lo is None else decimal.Decimal(lo)
    dhi = None if hi is None else decimal.Decimal(hi)

    cands = set()
    for base in [-31, -30.2, -8, -7.85, -7.8, -5.5, -4.5, -4.49, -1,
                 -0.75, -0.5, -0.25, 0, 0.24, 0.25, 0.5, 0.75, 0.76,
                 1, 1.4, 1.5, 2, 2.5, 3.5, 3.51, 5.5, 9, 10, 10.5, 12.5,
                 12.51, 17, 40163, 40163.125, 40163.13, 99999]:
        cands.add(str(decimal.Decimal(str(base))))
    for s in sorted(cands):
        v = decimal.Decimal(s)
        want = (dlo is None or v >= dlo) and (dhi is None or v <= dhi)
        assert accepts(nfa, s) == want, (s, lo, hi)
    # canonical form only
    assert not accepts(nfa, "01.5")
    assert not accepts(nfa, "1.")
    assert not accepts(nfa, "+2")
    # exponent form: accepted ONLY inside the safe box, so acceptance
    # implies the value is in range (safety direction of the subset)
    import itertools

    for m, e in itertools.product(["1", "2", "9.5"], range(-3, 4)):
        v = decimal.Decimal(m) * decimal.Decimal(10) ** e
        ok = (dlo is None or v >= dlo) and (dhi is None or v <= dhi)
        if accepts(nfa, f"{m}e{e}"):
            assert ok, (m, e, lo, hi)
    # trailing zeros are fine when the value is in range
    mid = dlo if dlo is not None else dhi
    if mid is not None:
        s = str(mid)
        if "." in s:
            assert accepts(nfa, s + "0") == (
                (dlo is None or mid >= dlo) and (dhi is None or mid <= dhi)
            )


def test_number_exclusive_bounds_are_subset():
    """Exclusive real bounds: the compiled language must EXCLUDE the
    boundary and stay within the open interval."""
    nfa = compile_schema(
        {"type": "number", "exclusiveMinimum": 1.5, "exclusiveMaximum": 4}
    )
    assert not accepts(nfa, "1.5")
    assert not accepts(nfa, "4")
    assert accepts(nfa, "2")
    assert accepts(nfa, "3.999")
    assert not accepts(nfa, "1.4")
    assert not accepts(nfa, "4.1")


def test_number_exclusive_bounds_arbitrary_depth():
    """Strict real bounds admit values arbitrarily close to the
    boundary but never the boundary itself (at any trailing-zero
    depth)."""
    nfa = compile_schema(
        {"type": "number", "exclusiveMinimum": 1.5, "exclusiveMaximum": 4}
    )
    for good in ["1.500001", "1.51", "3.9999999", "2", "3.5"]:
        assert accepts(nfa, good), good
    for bad in ["1.5", "1.50", "1.5000", "4", "4.0", "4.000", "1.49",
                "4.0001"]:
        assert not accepts(nfa, bad), bad


def test_number_negative_strict_zero():
    """maximum 0 strict => only negative values; "-0" variants equal
    zero and must be rejected."""
    nfa = compile_schema({"type": "number", "exclusiveMaximum": 0})
    for good in ["-0.001", "-1", "-99.5"]:
        assert accepts(nfa, good), good
    for bad in ["0", "0.0", "-0", "-0.0", "-0.000", "0.001"]:
        assert not accepts(nfa, bad), bad


def test_number_exponent_form_safe_box():
    """Bounded numbers admit canonical scientific form inside the
    exponent "safe box" (every mantissa in-range), so wide bounds don't
    force 300-digit positional output; boundary-adjacent decades stay
    positional-only (VERDICT r3 missing #7)."""
    # [5, 500]: safe exponents are exactly E=1 (10^1 >= 5, 10^2 <= 500)
    nfa = compile_schema({"type": "number", "minimum": 5, "maximum": 500})
    for good in ["1e1", "5e1", "9.99e1"]:
        assert accepts(nfa, good), good
    # in-bounds but outside the box (some mantissa at E=2 would exceed
    # 500) — positional still covers these values
    assert not accepts(nfa, "1e2")
    assert accepts(nfa, "100")
    for bad in ["1e0", "1e3", "4.9e0"]:  # out of bounds entirely
        assert not accepts(nfa, bad), bad

    # wide upper bound: exponent form reaches the top decades
    nfa = compile_schema({"type": "number", "minimum": 0, "maximum": 1e30})
    for good in ["1e5", "9.9e29", "2.5e10"]:
        assert accepts(nfa, good), good
    assert not accepts(nfa, "1e30")  # boundary decade: positional only
    assert accepts(nfa, "1" + "0" * 30)
    assert not accepts(nfa, "2e30")

    # negative side mirrors on magnitudes
    nfa = compile_schema(
        {"type": "number", "minimum": -1000, "maximum": -10}
    )
    for good in ["-1e1", "-9.9e2", "-2e2"]:
        assert accepts(nfa, good), good
    for bad in ["1e1", "-1e0", "-1e3", "-2e3"]:
        assert not accepts(nfa, bad), bad

    # strict bound at a power of ten excludes that exponent's floor
    nfa = compile_schema({"type": "number", "exclusiveMinimum": 100})
    assert accepts(nfa, "1e3")
    assert not accepts(nfa, "1e2")  # == 100 at m=1: excluded
    assert accepts(nfa, "100.5")

    # unbounded-above side: any exponent >= the safe floor
    nfa = compile_schema({"type": "number", "minimum": 10})
    for good in ["1e1", "3e25", "1e100"]:
        assert accepts(nfa, good), good
    assert not accepts(nfa, "1e0")


def test_number_bounds_edge_cases():
    """Negative-zero bounds compile (sign-strip regression) and
    astronomically wide bounds stay cheap (O(width) construction)."""
    import time

    nfa = compile_schema({"type": "number", "minimum": -0.0})
    assert accepts(nfa, "0") and accepts(nfa, "7.5")
    assert not accepts(nfa, "-1")

    t0 = time.monotonic()
    nfa = compile_schema({"type": "number", "minimum": 0,
                          "maximum": 1.7e308})
    dt = time.monotonic() - t0
    assert dt < 1.0, f"wide-bound compile took {dt:.2f}s"
    assert accepts(nfa, "12345.678")
    assert accepts(nfa, "9" * 300)
    assert not accepts(nfa, "-1")


@pytest.mark.parametrize(
    "schema,k,lo,hi",
    [
        ({"type": "integer", "multipleOf": 7}, 7, None, None),
        ({"type": "integer", "multipleOf": 5, "minimum": 3,
          "maximum": 100}, 5, 3, 100),
        ({"type": "integer", "multipleOf": 12, "minimum": -40,
          "maximum": 40}, 12, -40, 40),
        ({"type": "integer", "multipleOf": 9, "minimum": 17}, 9, 17, None),
        ({"type": "integer", "multipleOf": 4, "maximum": -6}, 4, None, -6),
    ],
)
def test_integer_multiple_of(schema, k, lo, hi):
    """multipleOf composes exactly with bounds via the remainder-
    tracking product automaton."""
    nfa = compile_schema(schema)
    for v in list(range(-130, 131)) + [252, 999, 1008, -1008]:
        want = (
            v % k == 0
            and (lo is None or v >= lo)
            and (hi is None or v <= hi)
        )
        assert accepts(nfa, str(v)) == want, (v, schema)
    assert not accepts(nfa, "014")


def test_multiple_of_empty_range_raises():
    with pytest.raises(ValueError, match="no multiple"):
        compile_schema(
            {"type": "integer", "multipleOf": 50, "minimum": 3,
             "maximum": 40}
        )


def test_fractional_multiple_of_warns_and_ignores():
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        nfa = compile_schema({"type": "integer", "multipleOf": 0.5})
        assert any("not enforced" in str(x.message) for x in w)
    assert accepts(nfa, "3")


def test_unique_items_enum_array():
    """uniqueItems + small enum items: repeats are impossible by
    construction; size bounds respected."""
    schema = {
        "type": "array",
        "items": {"enum": ["a", "b", "c"]},
        "uniqueItems": True,
        "minItems": 1,
        "maxItems": 2,
    }
    nfa = compile_schema(schema)
    enc = lambda a: json.dumps(a, separators=(",", ":"))  # noqa: E731
    for good in [["a"], ["c"], ["a", "b"], ["c", "a"]]:
        assert accepts(nfa, enc(good)), good
    for bad in [[], ["a", "a"], ["a", "b", "c"], ["d"], ["a", "d"]]:
        assert not accepts(nfa, enc(bad)), bad


def test_unique_items_large_pool_warns():
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        nfa = compile_schema(
            {
                "type": "array",
                "items": {"enum": list("abcdefgh")},
                "uniqueItems": True,
            }
        )
        assert any("uniqueItems" in str(x.message) for x in w)
    assert accepts(nfa, '["a","a"]')  # unchecked fallback


def test_unique_items_dedupes_enum_values():
    """Positional duplicates in the enum pool must not defeat the
    uniqueness guarantee."""
    nfa = compile_schema(
        {"type": "array", "items": {"enum": ["a", "a", "b"]},
         "uniqueItems": True, "minItems": 1}
    )
    assert accepts(nfa, '["a","b"]')
    assert not accepts(nfa, '["a","a"]')


# ---------------------------------------------------------------------------
# allOf intersection merge + additionalProperties
# ---------------------------------------------------------------------------


def test_allof_integer_bounds_brute_force():
    """Conjoined bounds + multipleOf from separate branches accept
    exactly their intersection — checked against int comparison."""
    nfa = compile_schema(
        {
            "allOf": [
                {"type": "integer", "minimum": -4},
                {"maximum": 10},
                {"multipleOf": 2},
            ]
        }
    )
    for v in range(-30, 31):
        want = -4 <= v <= 10 and v % 2 == 0
        assert accepts(nfa, str(v)) == want, v


def test_allof_merges_object_branches():
    """Properties and required sets union across branches; per-property
    schemas intersect recursively; emission keeps first-seen key order."""
    nfa = compile_schema(
        {
            "allOf": [
                {
                    "type": "object",
                    "properties": {"a": {"type": "integer", "minimum": 0}},
                    "required": ["a"],
                },
                {
                    "type": "object",
                    "properties": {
                        "a": {"maximum": 5},
                        "b": {"enum": ["x", "y"]},
                    },
                    "required": ["b"],
                },
            ]
        }
    )
    assert accepts(nfa, '{"a":3,"b":"x"}')
    assert accepts(nfa, '{"a":0,"b":"y"}')
    assert not accepts(nfa, '{"a":6,"b":"x"}')   # a > merged maximum
    assert not accepts(nfa, '{"a":-1,"b":"x"}')  # a < minimum
    assert not accepts(nfa, '{"a":3}')           # b required via union
    assert not accepts(nfa, '{"b":"x","a":3}')   # canonical key order


def test_allof_enum_intersection_and_lcm():
    nfa = compile_schema(
        {"allOf": [{"enum": [1, 2, 3, "x"]}, {"enum": [2, "x", 9]}]}
    )
    for text, want in [("2", True), ('"x"', True), ("1", False),
                       ("3", False), ("9", False)]:
        assert accepts(nfa, text) == want, text
    nfa = compile_schema(
        {"type": "integer", "allOf": [{"multipleOf": 4}, {"multipleOf": 6}],
         "minimum": 0, "maximum": 60}
    )
    for v in range(0, 61):
        assert accepts(nfa, str(v)) == (v % 12 == 0), v


def test_allof_type_intersection_number_integer():
    nfa = compile_schema(
        {"allOf": [{"type": "number"}, {"type": "integer"}]}
    )
    assert accepts(nfa, "7")
    assert not accepts(nfa, "7.5")


def test_allof_anyof_distribution():
    """allOf(anyOf(A,B), C) == anyOf(allOf(A,C), allOf(B,C)) — exact."""
    nfa = compile_schema(
        {
            "allOf": [
                {"anyOf": [{"minimum": 0}, {"maximum": -10}]},
                {"type": "integer", "maximum": 5},
            ]
        }
    )
    for v in range(-30, 31):
        want = (0 <= v <= 5) or (v <= -10)
        assert accepts(nfa, str(v)) == want, v


def test_allof_string_length_conjunction():
    nfa = compile_schema(
        {
            "allOf": [
                {"type": "string", "minLength": 2},
                {"maxLength": 4},
            ]
        }
    )
    for s, want in [("a", False), ("ab", True), ("abcd", True),
                    ("abcde", False)]:
        assert accepts(nfa, json.dumps(s)) == want, s


def test_pattern_length_bounds():
    """The bounds analyzer runs the real pattern compiler against a
    counting builder — spot-check it against known languages."""
    from sutro_tpu.engine.constrain.regex import (
        UnsupportedPattern,
        pattern_length_bounds,
    )

    assert pattern_length_bounds("^abc$") == (3, 3)
    assert pattern_length_bounds("^[a-z]{2,5}$") == (2, 5)
    assert pattern_length_bounds("^a+$") == (1, None)
    assert pattern_length_bounds("^a?(bc|defg)$") == (2, 5)
    assert pattern_length_bounds(r'^\d{4}-\d{2}$') == (7, 7)
    # unanchored ends wrap with star(string_char): unbounded above
    assert pattern_length_bounds("abc") == (3, None)
    assert pattern_length_bounds("^ab") == (2, None)
    with pytest.raises(UnsupportedPattern):
        pattern_length_bounds("^a(?=b)$")  # lookahead: outside subset


def test_allof_pattern_with_provable_length_bounds():
    """pattern + length bounds from different conjuncts: bounds the
    pattern provably satisfies are dropped as redundant; the pattern
    compiles and its language is emitted."""
    nfa = compile_schema(
        {
            "allOf": [
                {"type": "string", "pattern": "^[a-z]{3}$"},
                {"minLength": 2, "maxLength": 5},
            ]
        }
    )
    assert accepts(nfa, json.dumps("abc"))
    assert not accepts(nfa, json.dumps("ab"))
    assert not accepts(nfa, json.dumps("abcd"))


def test_allof_pattern_vs_length_bounds_hard_fails():
    """A pattern that cannot be proven to satisfy a length conjunct
    hard-fails (the merge's no-silent-widening contract) instead of
    letting compile_node drop the bounds."""
    with pytest.raises(ValueError, match="pattern"):
        compile_schema(
            {
                "allOf": [
                    {"type": "string", "pattern": "^a+$"},
                    {"maxLength": 4},
                ]
            }
        )


def test_allof_pattern_bounds_skipped_under_enum():
    """A merged enum/const makes the pattern-vs-length check moot:
    compile_node prefers the enum and the merge filters members against
    pattern AND bounds exactly — the schema must still compile."""
    nfa = compile_schema(
        {
            "allOf": [
                {"enum": ["aa", "aaaaaa"]},
                {"type": "string", "pattern": "^a+$"},
                {"maxLength": 4},
            ]
        }
    )
    assert accepts(nfa, json.dumps("aa"))
    assert not accepts(nfa, json.dumps("aaaaaa"))  # violates maxLength
    assert not accepts(nfa, json.dumps("bb"))


def test_allof_unsupported_pattern_keeps_length_bounds():
    """A pattern outside the regex subset inside allOf must NOT
    hard-fail against length conjuncts: compile_node's fallback
    enforces the bounds and warns the pattern is unenforced — exactly
    the non-allOf behavior, with no widening."""
    import warnings as _w

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        nfa = compile_schema(
            {
                "allOf": [
                    {"type": "string", "pattern": "^a(?=b)$"},
                    {"maxLength": 4},
                ]
            }
        )
    assert any("not enforced" in str(r.message) for r in rec)
    assert accepts(nfa, json.dumps("abcd"))
    assert not accepts(nfa, json.dumps("abcde"))  # bounds enforced


def test_direct_pattern_with_unprovable_bounds_warns():
    """Directly-authored pattern + bounds keeps the documented
    pattern-wins precedence but now warns when the bounds are not
    provably satisfied (they were silently dropped before)."""
    import warnings as _w

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        nfa = compile_schema(
            {"type": "string", "pattern": "^a+$", "maxLength": 4}
        )
    assert any("precedence" in str(r.message) for r in rec)
    assert accepts(nfa, json.dumps("aaaaaa"))  # pattern wins

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        compile_schema(
            {"type": "string", "pattern": "^a{1,3}$", "maxLength": 4}
        )
    assert not any("precedence" in str(r.message) for r in rec)


@pytest.mark.parametrize(
    "schema,msg",
    [
        ({"allOf": [{"type": "string"}, {"type": "integer"}]}, "type"),
        ({"allOf": [{"enum": [1]}, {"enum": [2]}]}, "enum"),
        ({"allOf": [{"const": 1}, {"const": 2}]}, "const"),
        (
            {"allOf": [{"type": "string", "pattern": "^a+$"},
                       {"pattern": "^b+$"}]},
            "pattern",
        ),
        (
            {"allOf": [{"oneOf": [{"type": "integer"}]},
                       {"minimum": 3}]},
            "oneOf",
        ),
        (
            {"allOf": [{"multipleOf": 2}, {"multipleOf": 0.5}]},
            "multipleOf",
        ),
    ],
)
def test_allof_unsupported_intersections_hard_fail(schema, msg):
    """Inexpressible conjunctions raise with a clear message instead of
    silently widening the language (subset discipline)."""
    with pytest.raises(ValueError, match=msg):
        compile_schema(schema)


def test_allof_pydantic_ref_with_siblings_still_works():
    """Pydantic's single-element allOf around a $ref plus annotation
    siblings (the pre-existing fast path) keeps working."""
    from enum import Enum

    from pydantic import Field

    class Color(str, Enum):
        red = "red"
        blue = "blue"

    class M(BaseModel):
        color: Color = Field(description="paint")

    nfa = compile_schema(normalize_output_schema(M))
    assert accepts(nfa, '{"color":"red"}')
    assert not accepts(nfa, '{"color":"green"}')


def test_additional_properties_false_closed_by_construction():
    """Declared-property objects never emit extra keys, so
    additionalProperties: false holds structurally."""
    nfa = compile_schema(
        {
            "type": "object",
            "properties": {"a": {"type": "integer"}},
            "required": ["a"],
            "additionalProperties": False,
        }
    )
    assert accepts(nfa, '{"a":1}')
    assert not accepts(nfa, '{"a":1,"z":2}')
    assert not accepts(nfa, '{"z":2,"a":1}')


def test_freeform_map_additional_properties_schema():
    """Property-less object with a value schema (Pydantic Dict[str, T])
    compiles to a free-form map instead of the empty object."""
    nfa = compile_schema(
        {"type": "object", "additionalProperties": {"type": "integer"}}
    )
    assert accepts(nfa, "{}")
    assert accepts(nfa, '{"k":1}')
    assert accepts(nfa, '{"k":1,"other":-2}')
    assert not accepts(nfa, '{"k":"s"}')
    assert not accepts(nfa, '{"k":1,}')
    bounded = compile_schema(
        {
            "type": "object",
            "additionalProperties": {"type": "boolean"},
            "minProperties": 1,
            "maxProperties": 2,
        }
    )
    assert not accepts(bounded, "{}")
    assert accepts(bounded, '{"k":true}')
    assert accepts(bounded, '{"k":true,"j":false}')
    assert not accepts(bounded, '{"a":true,"b":false,"c":true}')


def test_freeform_map_generation_completes():
    """Token-FSM drive over a byte vocabulary: masked sampling on a
    free-form map terminates with parseable, schema-valid JSON."""
    schema = {
        "type": "object",
        "additionalProperties": {"type": "integer"},
        "maxProperties": 2,
    }
    tok = ByteTokenizer()
    fsm = schema_constraint_factory(schema, tok)()
    rng = np.random.default_rng(3)
    out = bytearray()
    for _ in range(80):
        if fsm.is_complete():
            break
        ids = np.flatnonzero(fsm.allowed_tokens(remaining=80 - len(out)))
        assert len(ids), "dead state"
        t = int(rng.choice(ids))
        fsm.advance(t)
        out += tok.token_bytes(t)
    obj = json.loads(out.decode())
    assert all(isinstance(v, int) for v in obj.values())


def test_freeform_map_max_properties_above_16_enforced():
    """maxProperties is exact at any size (no silent star fallback)."""
    nfa = compile_schema(
        {"type": "object", "additionalProperties": {"type": "integer"},
         "maxProperties": 17}
    )
    ok = "{" + ",".join(f'"k{i}":1' for i in range(17)) + "}"
    too_many = "{" + ",".join(f'"k{i}":1' for i in range(18)) + "}"
    assert accepts(nfa, ok)
    assert not accepts(nfa, too_many)
    with pytest.raises(ValueError, match="minProperties"):
        compile_schema(
            {"type": "object", "additionalProperties": {},
             "minProperties": 20, "maxProperties": 18}
        )


def test_allof_enum_const_filtered_by_conjunct_bounds():
    """enum/const members violating a sibling conjunct's bounds are
    dropped (or the schema hard-fails as unsatisfiable) — the merge must
    never widen past the user's own validation."""
    nfa = compile_schema({"allOf": [{"enum": [1, 20]}, {"minimum": 10}]})
    assert accepts(nfa, "20")
    assert not accepts(nfa, "1")
    with pytest.raises(ValueError, match="const"):
        compile_schema({"allOf": [{"const": 5}, {"minimum": 10}]})
    nfa = compile_schema(
        {"allOf": [{"enum": ["a", "bb", "ccc"]},
                   {"type": "string", "minLength": 2, "maxLength": 2}]}
    )
    assert accepts(nfa, '"bb"')
    assert not accepts(nfa, '"a"')
    assert not accepts(nfa, '"ccc"')
    nfa = compile_schema(
        {"allOf": [{"enum": ["ab", "zz", 3]},
                   {"type": "string", "pattern": "^a"}]}
    )
    assert accepts(nfa, '"ab"')
    assert not accepts(nfa, '"zz"')
    assert not accepts(nfa, "3")  # type-filtered too


def test_allof_preserves_implicit_all_required():
    """A branch without an explicit required list keeps the compiler's
    all-properties-required default through the merge."""
    nfa = compile_schema(
        {
            "allOf": [
                {"type": "object", "properties": {"a": {"type": "integer"}}},
                {"type": "object",
                 "properties": {"b": {"type": "string"}},
                 "required": ["b"]},
            ]
        }
    )
    assert accepts(nfa, '{"a":1,"b":"x"}')
    assert not accepts(nfa, '{"b":"x"}')  # a implicitly required
    assert not accepts(nfa, '{"a":1}')


def test_allof_lone_oneof_with_annotation_siblings():
    """Annotation-only siblings (description etc.) must not make a lone
    oneOf conjunct 'inexpressible'."""
    nfa = compile_schema(
        {"allOf": [{"oneOf": [{"type": "integer"}]}],
         "description": "annotated"}
    )
    assert accepts(nfa, "7")


def test_allof_nested_anyof_does_not_leak():
    """A single-branch (or nested) anyOf conjunct must still intersect
    with its siblings instead of leaving an 'anyOf' key that makes
    compile_node drop them."""
    nfa = compile_schema(
        {
            "allOf": [
                {"anyOf": [{"anyOf": [{"type": "integer"},
                                      {"type": "string"}]}]},
                {"minimum": 3},
            ]
        }
    )
    assert accepts(nfa, "5")
    assert not accepts(nfa, "1")   # minimum survives the distribution
    assert accepts(nfa, '"ok"')    # string branch unaffected by minimum


def test_allof_composite_enum_filtered():
    """Array/object enum members are validated against conjunct
    composite constraints (recursively), not just scalar ones."""
    nfa = compile_schema(
        {"allOf": [{"enum": [[1], [1, 2, 3]]},
                   {"type": "array", "maxItems": 2}]}
    )
    assert accepts(nfa, "[1]")
    assert not accepts(nfa, "[1,2,3]")
    nfa = compile_schema(
        {"allOf": [{"enum": [{"a": 1}, {"a": 99}]},
                   {"type": "object",
                    "properties": {"a": {"maximum": 10}}}]}
    )
    assert accepts(nfa, '{"a":1}')
    assert not accepts(nfa, '{"a":99}')


def test_class_escaped_underscore_still_literal():
    """[\\_] — underscore is the one word-set member ECMA keeps a
    literal escape; must not fall back."""
    nfa = compile_schema(
        {"type": "object",
         "properties": {"s": {"type": "string", "pattern": r"^[\_a]+$"}},
         "required": ["s"]}
    )
    assert accepts(nfa, '{"s":"_a_"}')
    assert not accepts(nfa, '{"s":"b"}')


def test_allof_prunes_unsatisfiable_anyof_branches():
    """Optional-narrowing: allOf(anyOf(int, null), int&minimum) must
    keep the satisfiable branch, not fail the compile."""
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        nfa = compile_schema(
            {"allOf": [{"anyOf": [{"type": "integer"}, {"type": "null"}]},
                       {"type": "integer", "minimum": 0}]}
        )
        assert any("pruned" in str(x.message) for x in w)
    assert accepts(nfa, "3")
    assert not accepts(nfa, "-1")
    assert not accepts(nfa, "null")  # null branch correctly pruned
    with pytest.raises(ValueError, match="every distributed"):
        compile_schema(
            {"allOf": [{"anyOf": [{"type": "null"}, {"type": "boolean"}]},
                       {"type": "integer"}]}
        )


def test_allof_draft4_boolean_not_conflated_with_numeric():
    """True == 1 in Python; draft-4 boolean exclusive bounds normalize
    to the numeric form per conjunct, so (>5) ∧ (>1) merges to >5 —
    neither conflated with the number 1 nor re-attached to a bound
    tightened by a different conjunct."""
    nfa = compile_schema(
        {"type": "integer",
         "allOf": [{"minimum": 5, "exclusiveMinimum": True},
                   {"exclusiveMinimum": 1}]}
    )
    assert not accepts(nfa, "5")  # strict: 5 excluded
    assert accepts(nfa, "6")
    assert not accepts(nfa, "2")


def test_allof_draft4_flag_does_not_reattach_to_tightened_bound():
    """(>3) ∧ (>=5) must keep 5: the boolean flag from one conjunct may
    not make a DIFFERENT conjunct's minimum exclusive."""
    nfa = compile_schema(
        {"type": "integer",
         "allOf": [{"minimum": 3, "exclusiveMinimum": True},
                   {"minimum": 5}]}
    )
    assert accepts(nfa, "5")
    assert not accepts(nfa, "4")
    # same shape with an enum at the boundary value
    nfa = compile_schema(
        {"allOf": [{"minimum": 3, "exclusiveMinimum": True},
                   {"enum": [5], "minimum": 5}]}
    )
    assert accepts(nfa, "5")


def test_allof_integral_float_multipleof():
    nfa = compile_schema(
        {"allOf": [{"enum": [2, 3]}, {"multipleOf": 2.0}]}
    )
    assert accepts(nfa, "2")
    assert not accepts(nfa, "3")
    nfa = compile_schema(
        {"type": "integer", "minimum": 0, "maximum": 24,
         "allOf": [{"multipleOf": 4.0}, {"multipleOf": 6}]}
    )
    for v in range(0, 25):
        assert accepts(nfa, str(v)) == (v % 12 == 0), v


def test_allof_anyof_branch_object_keeps_implicit_required():
    """An object branch arriving through anyOf expansion keeps the
    all-properties-required default."""
    nfa = compile_schema(
        {"allOf": [
            {"anyOf": [{"type": "object",
                        "properties": {"a": {"type": "integer"}}}]},
            {"type": "object",
             "properties": {"b": {"type": "string"}},
             "required": ["b"]},
        ]}
    )
    assert accepts(nfa, '{"a":1,"b":"x"}')
    assert not accepts(nfa, '{"b":"x"}')


def test_allof_additional_properties_closure_across_conjuncts():
    """A conjunct's additionalProperties: false closes over ITS declared
    properties: a required extra from another conjunct is unsatisfiable;
    an optional extra is dropped (narrowing, never emitted)."""
    with pytest.raises(ValueError, match="additionalProperties"):
        compile_schema(
            {"allOf": [
                {"type": "object",
                 "properties": {"a": {"type": "integer"}},
                 "additionalProperties": False},
                {"type": "object",
                 "properties": {"b": {"type": "string"}},
                 "required": ["b"]},
            ]}
        )
    nfa = compile_schema(
        {"allOf": [
            {"type": "object",
             "properties": {"a": {"type": "integer"}},
             "additionalProperties": False},
            {"type": "object",
             "properties": {"b": {"type": "string"}},
             "required": []},
        ]}
    )
    assert accepts(nfa, '{"a":1}')
    assert not accepts(nfa, '{"a":1,"b":"x"}')  # b dropped by closure


def test_allof_map_value_schema_applies_to_merged_properties():
    """A map conjunct's value schema must constrain properties declared
    only by other conjuncts — string ∧ integer is unsatisfiable."""
    with pytest.raises(ValueError):
        compile_schema(
            {"allOf": [
                {"type": "object",
                 "additionalProperties": {"type": "integer"}},
                {"type": "object",
                 "properties": {"a": {"type": "string"}},
                 "required": ["a"]},
            ]}
        )
    nfa = compile_schema(
        {"allOf": [
            {"type": "object",
             "additionalProperties": {"minimum": 0}},
            {"type": "object",
             "properties": {"a": {"type": "integer", "maximum": 9}},
             "required": ["a"]},
        ]}
    )
    assert accepts(nfa, '{"a":5}')
    assert not accepts(nfa, '{"a":-3}')  # map conjunct's minimum applies


def test_allof_property_const_true_vs_1_not_conflated():
    with pytest.raises(ValueError, match="const"):
        compile_schema(
            {"allOf": [
                {"type": "object", "properties": {"a": {"const": True}},
                 "required": ["a"]},
                {"type": "object", "properties": {"a": {"const": 1}},
                 "required": ["a"]},
            ]}
        )


def test_allof_enum_dict_key_order_insensitive():
    """JSON-equal dict members with different key order intersect (no
    spurious empty-enum failure); the kept member emits in its own
    declared key order."""
    nfa = compile_schema(
        {"allOf": [{"enum": [{"a": 1, "b": 2}, 7]},
                   {"enum": [{"b": 2, "a": 1}]}]}
    )
    assert accepts(nfa, '{"b":2,"a":1}')
    assert not accepts(nfa, "7")


def test_allof_required_without_property_schema_hard_fails():
    with pytest.raises(ValueError, match="required"):
        compile_schema(
            {"allOf": [
                {"type": "object",
                 "properties": {"a": {"type": "integer"}},
                 "required": ["a"]},
                {"required": ["b"]},
            ]}
        )


def test_allof_fractional_multipleof_filters_enum():
    nfa = compile_schema(
        {"allOf": [{"enum": [1, 1.3]}, {"multipleOf": 0.5}]}
    )
    assert accepts(nfa, "1")
    assert not accepts(nfa, "1.3")


def test_freeform_map_honors_required_keys():
    nfa = compile_schema(
        {"type": "object", "additionalProperties": {"type": "integer"},
         "required": ["k"]}
    )
    assert not accepts(nfa, "{}")
    assert accepts(nfa, '{"k":1}')
    assert accepts(nfa, '{"k":1,"extra":2}')
    nfa = compile_schema(
        {"type": "object", "additionalProperties": {"type": "integer"},
         "required": ["k"], "maxProperties": 2}
    )
    assert accepts(nfa, '{"k":1,"x":2}')
    assert not accepts(nfa, '{"k":1,"x":2,"y":3}')
    with pytest.raises(ValueError, match="maxProperties"):
        compile_schema(
            {"type": "object", "additionalProperties": {},
             "required": ["a", "b"], "maxProperties": 1}
        )


# ---------------------------------------------------------------------------
# recursive schemas (self-referential Pydantic models)
# ---------------------------------------------------------------------------


def test_recursive_model_bounded_unrolling():
    """List['Node'] recursion compiles (no RecursionError): nesting
    accepted to MAX_REF_DEPTH, the cutoff closes child arrays to []."""
    from typing import List as TList

    class Node(BaseModel):
        name: str
        children: TList["Node"] = []

    nfa = compile_schema(normalize_output_schema(Node))
    assert accepts(nfa, '{"name":"a","children":[]}')
    assert accepts(
        nfa, '{"name":"a","children":[{"name":"b","children":[]}]}'
    )
    deep = '{"name":"a","children":[]}'
    for nm in ("b", "c", "d"):
        deep = (
            '{"name":"%s","children":[%s]}' % (nm, deep)
        )
    assert accepts(nfa, deep)  # depth == MAX_REF_DEPTH unrolls


def test_recursive_optional_keeps_null_arm():
    from typing import Optional as TOpt

    class Cell(BaseModel):
        v: int
        nxt: TOpt["Cell"] = None

    nfa = compile_schema(normalize_output_schema(Cell))
    assert accepts(nfa, '{"v":1}')
    assert accepts(nfa, '{"v":1,"nxt":{"v":2,"nxt":null}}')
    assert accepts(nfa, '{"v":1,"nxt":{"v":2,"nxt":{"v":3}}}')


def test_required_unbounded_recursion_hard_fails():
    """A required self-reference with no finite alternative cannot be
    finitely unrolled — clear ValueError, never a RecursionError."""
    with pytest.raises(ValueError, match="recursive"):
        compile_schema(
            {
                "$defs": {
                    "A": {
                        "type": "object",
                        "properties": {"next": {"$ref": "#/$defs/A"}},
                        "required": ["next"],
                    }
                },
                "$ref": "#/$defs/A",
            }
        )


def test_mutual_recursion_compiles_or_fails_cleanly():
    """A <-> B mutual recursion through an optional arm terminates."""
    schema = {
        "$defs": {
            "A": {
                "type": "object",
                "properties": {
                    "name": {"type": "string"},
                    "b": {"anyOf": [{"$ref": "#/$defs/B"},
                                    {"type": "null"}]},
                },
                "required": ["name"],
            },
            "B": {
                "type": "object",
                "properties": {
                    "a": {"anyOf": [{"$ref": "#/$defs/A"},
                                    {"type": "null"}]},
                },
                "required": ["a"],
            },
        },
        "$ref": "#/$defs/A",
    }
    nfa = compile_schema(schema)
    assert accepts(nfa, '{"name":"x"}')
    assert accepts(nfa, '{"name":"x","b":{"a":null}}')
    assert accepts(nfa, '{"name":"x","b":{"a":{"name":"y"}}}')


def test_recursive_ref_in_allof_wrapper():
    """Pydantic's Field()-metadata shape wraps the recursive ref in a
    single-element allOf — the depth counter must see through it."""
    schema = {
        "$defs": {
            "A": {
                "type": "object",
                "properties": {
                    "name": {"type": "string"},
                    "child": {
                        "anyOf": [
                            {"allOf": [{"$ref": "#/$defs/A"}],
                             "title": "Child"},
                            {"type": "null"},
                        ]
                    },
                },
                "required": ["name"],
            }
        },
        "$ref": "#/$defs/A",
    }
    nfa = compile_schema(schema)
    assert accepts(nfa, '{"name":"x"}')
    assert accepts(nfa, '{"name":"x","child":{"name":"y"}}')


def test_recursive_freeform_map_values():
    """Recursion through additionalProperties closes the map at the
    depth limit instead of RecursionError."""
    schema = {
        "$defs": {
            "A": {"type": "object",
                  "additionalProperties": {"$ref": "#/$defs/A"}}
        },
        "$ref": "#/$defs/A",
    }
    nfa = compile_schema(schema)
    assert accepts(nfa, "{}")
    assert accepts(nfa, '{"k":{}}')
    assert accepts(nfa, '{"k":{"j":{}}}')


def test_cpp_python_mask_parity_round3_features():
    """Native masker parity over the round-3 schema features (allOf
    merge, free-form map, recursion unrolling) — the NFA is the
    interchange format, so every new compile feature must flow through
    the C++ core bit-identically."""
    _assert_cpp_py_parity(
        {
            "$defs": {
                "N": {
                    "type": "object",
                    "properties": {
                        "v": {"allOf": [{"type": "integer", "minimum": 0},
                                        {"maximum": 20}]},
                        "kids": {"type": "array",
                                 "items": {"$ref": "#/$defs/N"}},
                        "tags": {"type": "object",
                                 "additionalProperties":
                                     {"type": "boolean"},
                                 "maxProperties": 2},
                    },
                    "required": ["v"],
                }
            },
            "$ref": "#/$defs/N",
        },
        '{"v":7,"kids":[{"v":20,"tags":{"a":true}}],"tags":{}}',
        expect_accept=True,
    )


@pytest.mark.parametrize(
    "schema",
    [
        # pure alias cycle: a def that IS a ref back to itself
        {"$defs": {"A": {"$ref": "#/$defs/A"}}, "$ref": "#/$defs/A"},
        # mutual alias cycle
        {"$defs": {"A": {"$ref": "#/$defs/B"},
                   "B": {"$ref": "#/$defs/A"}},
         "$ref": "#/$defs/A"},
        # cycle living entirely at allOf/anyOf level (bypasses
        # compile_node's per-node ref counter)
        {"$defs": {"U": {"anyOf": [{"allOf": [{"$ref": "#/$defs/U"}]},
                                   {"type": "null"}]}},
         "allOf": [{"$ref": "#/$defs/U"}]},
    ],
)
def test_ref_cycles_clear_error_never_recursionerror(schema):
    with pytest.raises(ValueError):
        compile_schema(schema)


# ---------------------------------------------------------------------------
# the packed answer (TokenFSM.allowed_packed) is allowed_tokens, bit for bit
# ---------------------------------------------------------------------------

# the classify template's schema (templates/classification.py: a
# scratchpad of at most 400 characters, then one of the labels)
_CLASSIFY = {
    "type": "object",
    "properties": {
        "scratchpad": {"type": "string", "maxLength": 400},
        "classification": {"enum": ["positive", "negative", "neutral"]},
    },
    "required": ["scratchpad", "classification"],
}
_ENUM_ONLY = {"enum": ["positive", "negative", "neutral"]}
_REGEX = {
    "type": "object",
    "properties": {
        "code": {"type": "string", "pattern": "^[A-Z]{2}-[0-9]{3,40}$"}
    },
    "required": ["code"],
}
# the template's shape with a scratchpad short enough to build at once
_SHORT = {
    "type": "object",
    "properties": {
        "scratchpad": {"type": "string", "maxLength": 24},
        "classification": {"enum": ["positive", "negative", "neutral"]},
    },
    "required": ["scratchpad", "classification"],
}
_PACKED_FACTORIES: dict = {}


def _packed_factory(name, vocab):
    key = (name, vocab)
    if key not in _PACKED_FACTORIES:
        schema = {"classify": _CLASSIFY, "enum": _ENUM_ONLY,
                  "regex": _REGEX, "short": _SHORT}[name]
        _PACKED_FACTORIES[key] = schema_constraint_factory(
            schema, ByteTokenizer(vocab_size=vocab)
        )
    return _PACKED_FACTORIES[key]


def _walk_packed(fac, budget):
    """Walk one row to completion under ``budget`` (None: no budget; an
    int: tokens left at step 0), a model that keeps writing where it may
    (a letter or a digit while one is allowed, else the first allowed
    id). At EVERY step, the complete state included, the packed answer
    must unpack to ``allowed_tokens``'s, with and without a ``shared``
    dict. Returns [(filtered, cached array?)] a step."""
    V = fac.table.vocab_size
    fsm, twin = fac(), fac()
    seen = []
    for n in range(1200):
        rem = None if budget is None else max(budget - n, 0)
        want = fsm.allowed_tokens(remaining=rem)
        bits, filtered = fsm.allowed_packed(remaining=rem)
        assert bits.dtype == np.uint8 and bits.shape == ((V + 7) // 8,)
        np.testing.assert_array_equal(
            np.unpackbits(bits, count=V).astype(bool), want
        )
        # rows of one assembly in one state under one budget share the
        # filtered row; a kept array is the same object either way
        shared: dict = {}
        a, fa = fsm.allowed_packed(remaining=rem, shared=shared)
        b, fb = twin.allowed_packed(remaining=rem, shared=shared)
        assert a is b and fa == fb == filtered
        np.testing.assert_array_equal(a, bits)
        kept = fsm._complete or bits is fsm.masks.entry(fsm.states).packed
        assert kept == (not filtered) and kept == (not bits.flags.writeable)
        seen.append(filtered)
        if fsm.is_complete():
            return seen
        tid = next(
            (t for t in (ord("a"), ord("7")) if want[t]),
            int(np.argmax(want)),
        )
        fsm.advance(tid)
        twin.advance(tid)
    raise AssertionError("the walk did not complete")


@pytest.mark.parametrize("budget", ["none", "roomy", "tight", "infeasible"])
@pytest.mark.parametrize(
    "name,vocab",
    # 267 ids: not a multiple of 8 (the template's NFA is seconds to
    # build, so it is walked at that one)
    [("classify", 267), ("enum", 267), ("enum", 272), ("regex", 267),
     ("regex", 272)],
)
def test_allowed_packed_is_allowed_tokens_bit_for_bit(name, vocab, budget):
    fac = _packed_factory(name, vocab)
    room = fac().min_tokens()
    seen = _walk_packed(
        fac,
        {"none": None, "roomy": room + 1000, "tight": room + 4,
         "infeasible": 2}[budget],
    )
    assert seen[-1] is False  # the complete state: the kept stop mask
    if budget in ("none", "roomy"):
        # a budget that never bites never leaves the cache
        assert not any(seen)
    if budget == "tight" and name != "enum":
        # the budget bites on the last free tokens before the forced close
        assert any(seen) and not seen[0]
    if budget == "infeasible":
        # infeasible from the start: the unfiltered mask, from the cache
        assert not seen[0]


@pytest.mark.parametrize(
    "tok_vocab,model_vocab",
    [
        (267, 267),   # equal, the last byte partly used
        (267, 512),   # the model's vocabulary padded past the tokenizer's
        (267, 269),   # ... inside the tokenizer's last byte
        (272, 301),   # ... from a whole byte on, into a partial one
        (300, 267),   # a tokenizer wider than the model: cut at the model's
        (272, 267),   # ... inside the same byte
    ],
)
def test_packed_row_pads_and_cuts_like_the_bool_mask(tok_vocab, model_vocab):
    """The scheduler's packed row against the path it replaces
    (``_pad_mask`` of the bool mask, then ``np.packbits``): the ids past
    the tokenizer's vocabulary False, none past the model's."""
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    b = object.__new__(ContinuousBatcher)
    b.vocab = model_vocab
    b._ones_row = np.packbits(np.ones((model_vocab,), bool))
    fac = _packed_factory("short", tok_vocab)
    fsm = fac()
    tight = fsm.min_tokens() + 4
    filtered = 0
    for n in range(40):
        for rem in (None, 1000, max(tight - n, 0)):
            out = b._ones_packed(1)[0]
            how = b._constraint_packed(fsm, rem, out, {})
            want = b._constraint_mask(fsm, rem)
            assert want.shape == (model_vocab,)
            np.testing.assert_array_equal(out, np.packbits(want))
            assert how in ("cached", "filtered")
            filtered += how == "filtered"
        allowed = fsm.allowed_tokens()
        fsm.advance(ord("a") if allowed[ord("a")] else int(np.argmax(allowed)))
    assert filtered  # the tight budget bit on the way
