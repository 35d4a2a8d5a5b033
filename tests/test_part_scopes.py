"""Every device op of a step program carries the PART of the model that
issued it (``sutro_tpu/ops/lowering.py`` ``PARTS``): read here, at tiny
sizes on the CPU, from the OPTIMIZED HLO of the prefill, the single step
(FSM-masked) and the fused window of a dense model (the homogeneous
scan), a routed one, and one mixed-walk model of each state kind
(conv: tiny-lfm2, Mamba-2: tiny-granite, delta rule: tiny-solar-kda).

What is checked of every instruction that RUNS (the entry computation,
loop bodies and conditions, branches, calls; not what a fusion fuses nor
a reducer or comparator applies): a ``dot``, ``convolution``, ``reduce``,
``sort``, ``gather``, ``scatter``, custom call or ``dynamic-update-slice``
of more than a scalar carries a part, fused or not (a fusion as
``perfbench/trace_parts.py`` reads it: by its own ``op_name``, else by
what it fuses). The one exception is named, not waved through: what a
``lax.scan`` does ITSELF (stacking its ys, slicing its xs) carries the
scan's name stack and no scope opened inside its body can reach it
(``_SCANS_OWN``); it stays unnamed and is measured as such
(``device_unnamed_share``). Whatever else is left without a part is
listed by opcode (``BOOKKEEPING``).
"""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from perfbench import trace_parts
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import lowering

HEAVY = frozenset((
    "dot", "convolution", "reduce", "sort", "gather", "scatter",
    "custom-call", "dynamic-update-slice",
))
#: opcodes an instruction without a part may have: loop counters and
#: conditions, tuples, bitcasts and copies of a carry, constants
#: broadcast, the scans' own slices, and the CPU backend's rewrite of a
#: cumulative sum (``reduce-window``, which drops the metadata)
BOOKKEEPING = frozenset((
    "while", "conditional", "call", "copy", "bitcast", "tuple",
    "get-tuple-element", "parameter", "constant", "broadcast", "iota",
    "add", "compare", "select", "slice", "dynamic-slice", "reshape",
    "convert", "transpose", "concatenate", "pad", "reduce-window",
    "and", "or", "xor", "not", "multiply", "subtract", "minimum", "maximum",
    "(rewritten)",
))
#: instructions that never run as an op of their own
_NO_OP = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
))
#: the ``op_name`` of what a scan does itself ends in one of these
_SCANS_OWN = re.compile(r"(^|/)while/body/(closed_call/)?"
                        r"(dynamic_update_slice|dynamic_slice|squeeze)$")
KERNELS = {
    "paged_decode_attention": "mixer", "kv_write_pallas": "cache",
    "row_write_pallas": "cache", "grouped_matmul": "ffn",
    "ssm_state_read": "mixer", "kda_state_read": "mixer",
    "kda_state_commit": "cache",
}

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$"
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERS = re.compile(
    r"(calls|to_apply|body|condition|branch_computations|true_computation|"
    r"false_computation)=(\{[^}]*\}|%?[\w.\-]+)"
)
_SCALAR = re.compile(r"^\(?\w+\[\](\{[^}]*\})?\)?$")


def parse(text):
    """``({computation: [instruction]}, entry)`` of an optimized HLO."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
            continue
        if line.startswith("}"):
            cur = None
        got = _INSTRUCTION.match(line) if cur else None
        if not got:
            continue
        name, shape, opcode, rest = got.groups()
        meta = _OP_NAME.search(rest)
        comps[cur].append(dict(
            name=name, opcode=opcode, line=line.strip(),
            scalar=bool(_SCALAR.match(shape.strip())),
            op_name=meta.group(1) if meta else "",
            refs={
                k: [x.strip().lstrip("%") for x in v.strip("{}").split(",")]
                for k, v in _REFERS.findall(rest)
            },
        ))
    return comps, entry


def running(comps, entry):
    """The computations whose instructions run as ops of their own."""
    seen, todo = [], [entry]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.append(c)
        for ins in comps[c]:
            for kind, names in ins["refs"].items():
                if kind == "calls" or (
                    kind == "to_apply" and ins["opcode"] != "call"
                ):
                    continue
                todo.extend(names)
    return seen


def fused(comps, ins):
    out = []
    for c in ins["refs"].get("calls", ()):
        for sub in comps.get(c, ()):
            out.append(sub)
            if sub["opcode"] == "fusion":
                out += fused(comps, sub)
    return out


def audit(text):
    """``(parts of the running instructions, opcodes left without one,
    heavy instructions left without one, every (part, the op_name it is
    read under, instruction, what it fuses))``."""
    comps, entry = parse(text)
    by_part, left, bad, rows = collections.Counter(), set(), [], []
    for c in running(comps, entry):
        for ins in comps[c]:
            inner = fused(comps, ins) if ins["opcode"] == "fusion" else []
            read_as = trace_parts.read_as(
                ins["op_name"], [s["op_name"] for s in inner]
            )
            part = trace_parts.part_of(read_as)
            if ins["opcode"] not in _NO_OP:
                by_part[part] += 1
            rows.append((part, read_as, ins, inner))
            if part is not None:
                continue
            heavy = [
                s["opcode"] for s in [ins] + inner
                if s["opcode"] in HEAVY and not s["scalar"]
            ]
            # an instruction with NO metadata is one a backend pass made
            # (the CPU's rewrite of a batched HIGHEST-precision dot): no
            # scope of the program could have reached it
            if heavy and ins["op_name"] and not _SCANS_OWN.search(
                ins["op_name"]
            ):
                bad.append((heavy, ins["line"][:240]))
            if ins["opcode"] != "fusion":
                left.add(ins["opcode"] if ins["op_name"] else "(rewritten)")
            else:
                left.update(s["opcode"] for s in inner)
    return by_part, left, bad, rows


def check_kernels_under_their_parts(op_names):
    """Wherever a kernel's name stands in an ``op_name``, the part named
    for it stands before it."""
    seen = set()
    for op_name in op_names:
        for kernel, part in KERNELS.items():
            at = op_name.find(kernel)
            if at < 0:
                continue
            seen.add(kernel)
            assert trace_parts.part_of(op_name[:at]) == part, op_name
    return seen


def test_the_benchmarks_parts_are_the_programs():
    assert trace_parts.PARTS == lowering.PARTS
    with pytest.raises(AssertionError):
        with lowering.part("attention"):
            pass


@functools.lru_cache(maxsize=None)
def _programs(model):
    """The optimized HLO of a tiny runner's prefill, masked single step
    and fused window."""
    mcfg = MODEL_CONFIGS[model]
    ecfg = EngineConfig(
        kv_page_size=8, max_pages_per_seq=16, decode_batch_size=4,
        max_model_len=128, use_pallas=False, param_dtype="float32",
        decode_multi_step=4,
    )
    r = ModelRunner(mcfg, ecfg)
    B, MP = 4, 16

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    def f32(*shape):
        return jnp.zeros(shape, jnp.float32)

    key = jax.random.PRNGKey(0)
    masks = jnp.zeros((B, (mcfg.vocab_size + 7) // 8), jnp.uint8)
    lowered = {
        "prefill": ModelRunner._prefill_jit.lower(
            r, r.params, r.cache, i32(2, 16), i32(2), i32(2, MP), i32(2)
        ),
        "step": ModelRunner._decode_jit.lower(
            r, r.params, r.cache, i32(B, 1), i32(B), i32(B, MP), key,
            f32(B), f32(B), i32(B), masks, None,
        ),
        "window": ModelRunner._decode_multi_jit.lower(
            r, r.params, r.cache, i32(B), i32(B), i32(B, MP), key,
            f32(B), f32(B), 4, i32(B), 1, None,
        ),
    }
    return {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.mark.parametrize("model,kinds", [
    ("tiny-dense", {"mixer": "attn_mixer", "ffn": "dense_ffn"}),
    ("tiny-moe", {"mixer": "attn_mixer", "ffn": "moe_ffn"}),
    ("tiny-lfm2", {"mixer": "conv_mixer", "ffn": "moe_ffn"}),
    ("tiny-granite", {"mixer": "mamba_mixer", "ffn": "dense_ffn"}),
    ("tiny-solar-kda", {"mixer": "kda_mixer", "ffn": "moe_ffn"}),
])
def test_every_op_of_a_step_program_carries_its_part(model, kinds):
    for program, text in _programs(model).items():
        by_part, left, bad, rows = audit(text)
        assert not bad, (model, program, bad)
        assert left <= BOOKKEEPING, (model, program, left - BOOKKEEPING)
        # every part is there, and the scopes of a kind nest INSIDE it
        wanted = set(lowering.PARTS)
        if program == "prefill":
            wanted -= {"sample"}   # admission samples, in a program of its own
        assert wanted <= set(by_part), (model, program, dict(by_part))
        scopes = {
            (part, trace_parts.scopes_of(name).split("/")[0])
            for part, name, _ins, _inner in rows if part
        }
        for part, kind in kinds.items():
            assert (part, kind) in scopes, (model, program, part, kind)

        # the writes of a pool or a state are the cache's; the other
        # scatters (a routed layer's rows, a slot map, the penalties'
        # counts) are their own part's, none is without one
        scatters = {
            part for part, _name, ins, inner in rows
            if "scatter" in [ins["opcode"]] + [f["opcode"] for f in inner]
        }
        assert "cache" in scatters and None not in scatters


def test_a_masked_steps_top_k_is_the_samplers_and_the_logits_the_heads():
    text = _programs("tiny-dense")["step"]
    _by_part, _left, _bad, rows = audit(text)
    tops = [
        (p, n) for p, n, ins, _inner in rows
        if "top_k" in n and ins["opcode"] != "parameter"
    ]
    assert tops and {p for p, _n in tops} == {"sample"}, tops
    vocab = str(MODEL_CONFIGS["tiny-dense"].vocab_size)
    logits = [
        p for p, n, ins, _inner in rows
        if ins["opcode"] == "dot" and re.search(rf"\[4,(1,)?{vocab}\]", ins["line"])
    ]
    assert logits and set(logits) == {"head"}, logits
    # the decode attention (its softmax) is the mixer's
    assert {p for p, n, _i, _f in rows if "paged_decode_xla" in n} == {"mixer"}
    # unpacking the FSM masks is the sampler's
    assert {
        p for p, n, _i, _f in rows if "unpack_mask" in n or "unpackbits" in n
    } <= {"sample"}
