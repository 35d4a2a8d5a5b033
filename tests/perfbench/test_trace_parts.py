"""``perfbench/trace_parts.py``: the walk of a profiler trace's own HLO
(on a small ``.xplane.pb`` recorded on the CPU here, by a jitted function
with two parts, an inner scope and a scan), ``part_of`` / ``scopes_of`` /
``read_as``, the self-time rule under a ``while`` and the average over
device planes (on ``perfbench/data/recorded_trace.json``, the small
trace the reduction is tested on, with the names a program WITH the
scopes would give its ops), and the four readers: nothing to read on a
trace without parts, addends of ``decode_step_device_ms`` on one with.
"""

import copy
import json
import re
import types
from pathlib import Path

import pytest

from perfbench import trace_parts, trace_reduce
from perfbench.layer_metrics import (
    decode_ffn_ms_per_step, decode_head_ms_per_step,
    decode_mixer_ms_per_step, decode_step_device_ms, device_unnamed_share,
)

REPO = Path(__file__).resolve().parents[2]
RECORDED = json.loads(
    (REPO / "perfbench/data/recorded_trace.json").read_text()
)
READERS = (decode_mixer_ms_per_step, decode_ffn_ms_per_step,
           decode_head_ms_per_step, device_unnamed_share)

#: what the recorded trace's ops would be called by a program with the
#: scopes: the decode module's ``while`` is the layer scan (no part: a
#: loop's own time), under it a mixer's fusion and kernel, the ffn's
#: collective and the head; the prefill module's copy is hoisted
DECODE = "jit__decode_multi_jit(101)"
NAMES = {
    DECODE: {
        "while.1": "jit(_decode_multi_jit)/while",
        "fusion.1": "jit(_decode_multi_jit)/while/body/mixer/attn_mixer/dot_general",
        "custom-call.2": "jit(_decode_multi_jit)/while/body/mixer/attn_mixer/"
                         "jit(paged_decode_attention)/pallas_call",
        "all-reduce.3": "jit(_decode_multi_jit)/while/body/ffn/dense_ffn/psum",
        "fusion.4": "jit(_decode_multi_jit)/head/dot_general",
    },
    "jit__prefill_jit(102)": {
        "fusion.5": "jit(_prefill_jit)/cache/jit(kv_write_pallas)/pallas_call",
        "copy.6": "",
    },
}


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    """A trace of three calls of a small jitted function, recorded here."""
    import jax
    import jax.numpy as jnp

    from sutro_tpu.ops.lowering import part

    @jax.jit
    def two_parts(x, w):
        with part("mixer"), jax.named_scope("attn_mixer"):
            y = x @ w
        with part("ffn"):
            z = jnp.tanh(y) @ w

        def body(c, _):
            with part("head"):
                return c @ w, None

        return jax.lax.scan(body, z, None, length=3)[0]

    x = jnp.ones((32, 32))
    two_parts(x, x).block_until_ready()
    out = tmp_path_factory.mktemp("perfbench-trace-parts")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    for _ in range(3):
        two_parts(x, x).block_until_ready()
    jax.profiler.stop_trace()
    files = sorted(out.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    return str(files[-1]), two_parts.lower(x, x).compile().as_text()


def test_the_wire_walk_reads_the_traces_own_hlo(xplane):
    path, text = xplane
    protos = trace_parts.hlo_protos(path)
    module = next(m for m in protos if m.startswith("jit_two_parts"))
    assert re.fullmatch(r"jit_two_parts\(\d+\)", module)
    ops = trace_parts.hlo_op_names(path)[module]
    # every instruction of the compiled text that carries an op_name is in
    # the walk under the same one
    in_text = dict(re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"', text, re.M
    ))
    assert len(in_text) > 8
    for name, op_name in in_text.items():
        got = ops[name]
        assert got == op_name or trace_parts.part_of(op_name) is None, name
    by_part = {
        p: [n for n, o in ops.items() if trace_parts.part_of(o) == p]
        for p in ("mixer", "ffn", "head")
    }
    assert all(by_part.values()), by_part
    assert any(
        trace_parts.scopes_of(ops[n]) == "attn_mixer" for n in by_part["mixer"]
    )
    # the scan's body is under its part THROUGH the loop
    assert any("while/body" in ops[n] for n in by_part["head"])
    assert trace_parts.part_of(ops[next(n for n in ops if n.startswith("while"))]) is None


def test_fields_walks_varints_bytes_and_fixed_widths():
    # 1: varint 300; 2: bytes "ab"; 3: fixed32; 4: fixed64; 1 again
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x1D, 1, 0, 0, 0, 0x21, 2, 0, 0, 0, 0, 0, 0, 0, 0x08, 0x01])
    got = [(k, v if isinstance(v, int) else bytes(v))
           for k, v in trace_parts.fields(buf)]
    assert got == [(1, 300), (2, b"ab"), (3, b"\x01\0\0\0"),
                   (4, b"\x02" + b"\0" * 7), (1, 1)]
    with pytest.raises(ValueError):
        list(trace_parts.fields(bytes([0x0B])))   # a group: not in proto3
    assert trace_parts.instructions(b"") == {}


@pytest.mark.parametrize("op_name,part,scopes", [
    ("jit(f)/while/body/closed_call/mixer/attn_mixer/dot_general", "mixer", "attn_mixer"),
    ("jit(f)/mixer/mla_mixer/mla_absorb/dsa_attend/jit(_where)/select_n", "mixer",
     "mla_mixer/mla_absorb/dsa_attend"),
    ("jit(f)/ffn/moe_ffn/shared_expert/dot_general", "ffn", "moe_ffn/shared_expert"),
    ("jit(f)/cache/cache/jit(kv_write_pallas)/pallas_call", "cache", ""),
    ("jit(f)/cache/kda_commit/cond/branch_1_fun/mul", "cache", "kda_commit"),
    ("jit(f)/jit(sample)/mixer_state/add", None, ""),       # not bare
    ("jit(f)/while/body/dynamic_slice", None, ""),           # the scan's own
    ("jit(f)/sample/top_k", "sample", ""),
    ("jit(f)/mixer/mamba_mixer/while/body/mamba_mixer/mamba_mixer/mul", "mixer",
     "mamba_mixer"),
    ("jit(f)/mixer/ffn/dot_general", "mixer", ""),           # the OUTERMOST
    ("", None, ""), (None, None, ""),
])
def test_part_of_and_scopes_of(op_name, part, scopes):
    assert trace_parts.part_of(op_name) == part
    assert trace_parts.scopes_of(op_name) == scopes


def test_a_fusion_is_read_by_its_own_name_else_by_what_it_fuses():
    own = "jit(f)/ffn/dense_ffn/dot_general"
    inner = ["jit(f)/mixer/a/mul", "jit(f)/sample/exp", "jit(f)/sample/sub", ""]
    assert trace_parts.read_as(own, inner) == own
    # no part of its own: the part most of the fused instructions carry
    assert trace_parts.read_as("jit(f)/while/body/dynamic_update_slice", inner) \
        == "jit(f)/sample/exp"
    assert trace_parts.read_as("", inner) == "jit(f)/sample/exp"
    # a tie goes to the part named first in PARTS
    assert trace_parts.read_as("", inner[:2]) == "jit(f)/mixer/a/mul"
    assert trace_parts.read_as("x/y", ["", "a/b"]) == "x/y"
    assert trace_parts.read_as("x/y", []) == "x/y"
    # an instruction a compiler pass made and named itself (the expansion
    # of a ragged_dot), or left without a name: by its operands
    rows = ["jit(f)/ffn/moe_ffn/sort", "params['we_up']", "jit(f)/ffn/moe_ffn/cumsum"]
    assert trace_parts.read_as("ragged-dot-none", [], rows) == rows[0]
    assert trace_parts.read_as("", [], rows) == rows[0]
    assert trace_parts.read_as("", [], ["params['wq']"]) == ""
    # what a scan does itself is a JAX path: its operands do not name it
    own = "jit(f)/while/body/dynamic_update_slice"
    assert trace_parts.read_as(own, [], ["jit(f)/while/body/mixer/k"]) == own


def rows(trace=RECORDED, names=NAMES, modules=None, window=None):
    return trace_parts.of_modules(trace_parts.op_rows(
        trace, names, window or trace_reduce.window_of(trace)
    ), modules)


def test_a_while_does_not_count_its_body_twice():
    got = trace_parts.by_part(rows(modules=r"decode"))
    # while.1 lasts 1000 ns and its four ops fill it: its self time is 0
    assert got[None] == pytest.approx(0.0)
    assert got["mixer"] == pytest.approx(500e-9)   # fusion.1 + custom-call.2
    assert got["ffn"] == pytest.approx(100e-9)
    assert got["head"] == pytest.approx(400e-9)
    assert sum(got.values()) == pytest.approx(1000e-9)
    # every program: the prefill's kernel is the cache's, its copy nobody's
    every = trace_parts.by_part(rows())
    assert every["cache"] == pytest.approx(500e-9)
    assert every[None] == pytest.approx(400e-9)
    # an op outside every module has no module and no part
    lost = copy.deepcopy(RECORDED)
    lost["devices"]["/device:TPU:0"]["ops"].append(["fusion.1", 1200, 50, "fusion"])
    assert ("", "fusion.1", "", pytest.approx(50e-9)) in rows(lost)


def test_an_op_across_the_windows_edge_counts_its_part_inside():
    got = trace_parts.by_part(rows(modules=r"decode", window=(150.0, 2500.0)))
    assert got["mixer"] == pytest.approx(350e-9)   # half of fusion.1 is before
    assert got[None] == pytest.approx(0.0)


def test_two_device_planes_average():
    two = copy.deepcopy(RECORDED)
    second = copy.deepcopy(two["devices"]["/device:TPU:0"])
    second["ops"][1][2] = 100     # its fusion.1 is shorter: the while idles
    two["devices"]["/device:TPU:1"] = second
    got = trace_parts.by_part(rows(two, modules=r"decode"))
    assert got["mixer"] == pytest.approx((500e-9 + 300e-9) / 2)
    assert got[None] == pytest.approx(200e-9 / 2)   # the while's own time
    assert got["head"] == pytest.approx(400e-9)
    # a module the names know only by its key (another run's id)
    other = {"jit__decode_multi_jit(7)": NAMES[DECODE]}
    assert trace_parts.by_part(rows(names=other, modules=r"decode")) \
        == trace_parts.by_part(rows(modules=r"decode"))


def reading(monkeypatch, names, trace=RECORDED, steps=8):
    """What the four readers look at, with the trace file's parse put in
    its place."""
    reduced = trace_reduce.reduce_trace(trace)
    spans = [("decode_window", 1.0, 1.5, {"steps": steps})]
    r = types.SimpleNamespace(
        trace=reduced, t0=0.0, spans=spans, trace_span=(0.0, 100.0),
    )
    r.spans_in_trace = lambda name: [s for s in spans if s[0] == name]
    monkeypatch.setattr(trace_parts, "newest_trace", lambda since=0.0: "recorded")
    monkeypatch.setattr(trace_parts, "parsed", lambda path: (trace, names))
    trace_parts._rows.cache_clear()                    # made once a process
    return r


def test_a_trace_without_parts_reads_nothing(monkeypatch):
    """The parent of the PR that brought the parts: the mixed walk's
    scopes are there, no part is; and a trace that describes no module."""
    scoped = {
        m: {k: v.replace("/mixer/", "/").replace("/ffn/", "/")
            .replace("/head/", "/").replace("/cache/", "/")
            for k, v in ops.items()}
        for m, ops in NAMES.items()
    }
    assert "attn_mixer" in scoped[DECODE]["fusion.1"]
    # JAX names a few ops of its own ``.../sample/reduce`` (seen in the
    # parent's chip trace, PR 52): a stray part is not the vocabulary
    scoped[DECODE]["fusion.4"] = "jit(_decode_multi_jit)/while/body/sample/reduce"
    for names in (scoped, {}):
        r = reading(monkeypatch, names)
        assert decode_step_device_ms.read(r) is not None
        assert [mod.read(r) for mod in READERS] == [None] * 4
    # an untraced run, and a traced one whose file is gone
    r = reading(monkeypatch, NAMES)
    r.trace = None
    assert [mod.read(r) for mod in READERS] == [None] * 4
    r = reading(monkeypatch, NAMES)
    monkeypatch.setattr(trace_parts, "newest_trace", lambda since=0.0: None)
    assert [mod.read(r) for mod in READERS] == [None] * 4


def test_the_parts_of_a_decode_step_add_up_to_it(monkeypatch):
    r = reading(monkeypatch, NAMES)
    whole = decode_step_device_ms.read(r)
    assert whole == pytest.approx(1000e-9 * 1e3 / 8)
    mixer, ffn, head = (m.read(r) for m in READERS[:3])
    assert (mixer, ffn, head) == pytest.approx(
        (500e-6 / 8, 100e-6 / 8, 400e-6 / 8)
    )
    rest = trace_parts.seconds_by_part(r, decode_step_device_ms.MODULES)
    others = sum(rest.get(p, 0.0) for p in ("cache", "embed", None)) * 1e3 / 8
    assert mixer + ffn + head + others == pytest.approx(whole)
    assert max(mixer, ffn, head) < whole
    # of every program's busy time, the prefill's hoisted copy is unnamed
    assert device_unnamed_share.read(r) == pytest.approx(100 * 400 / 1900)


def test_the_trace_is_found_where_the_tracer_puts_it(monkeypatch, tmp_path):
    import os
    import time

    monkeypatch.setattr(trace_parts.tempfile, "gettempdir", lambda: str(tmp_path))
    assert trace_parts.newest_trace() is None
    old = tmp_path / "perfbench-trace-a/plugins/profile/t1/h.xplane.pb"
    new = tmp_path / "perfbench-trace-b/plugins/profile/t2/h.xplane.pb"
    for f in (old, new):
        f.parent.mkdir(parents=True)
        f.write_bytes(b"")
    os.utime(old, (time.time() - 500, time.time() - 500))
    assert trace_parts.newest_trace() == str(new)
    assert trace_parts.newest_trace(time.time() - 1000) == str(new)
    os.utime(new, (time.time() - 100, time.time() - 100))
    assert trace_parts.newest_trace(time.time() - 50) is None
    # an empty file parses to nothing, once
    trace, names = trace_parts.parsed(str(new))
    assert names == {} and trace_parts.parsed(str(new))[0] is trace
    trace_parts.parsed.cache_clear()


def test_the_tools_table_is_by_program_part_and_scope():
    from perfbench.tools import part_table

    runs = trace_reduce.reduce_trace(RECORDED)["module_s"]
    doc = part_table.table(rows(), runs, steps=8)
    step = doc["jit__decode_multi_jit"]
    assert (step["runs"], step["unit"]) == (1.0, "step")
    assert step["ms"] == pytest.approx(1000e-6 / 8, abs=1e-4)
    assert list(step["by_part_ms"])[0] == "mixer"
    assert set(step["by_scope_ms"]) == {
        "mixer/attn_mixer", "ffn/dense_ffn", "head/"
    }
    assert "custom-call" in step["top_ops_ms"]["mixer"]
    prefill = doc["jit__prefill_jit"]
    assert prefill["unit"] == "run"
    assert [u[0] for u in prefill["unnamed_ops_ms"]] == ["copy.6"]
    assert part_table.stale(doc) == []
    lines = []
    part_table.show(doc, lines.append)
    assert any(line.startswith("jit__decode_multi_jit: 1 runs") for line in lines)
    # a decode program without a part: the stale-cache warning
    bare = part_table.table(rows(names={}), runs, steps=8)
    assert part_table.stale(bare) == ["jit__decode_multi_jit"]
    part_table.show(bare, lines.append)
    assert "stale compile cache" in lines[-1]
