"""Compile for a described v5e (no chip attached) the fused decode window
of the two benchmark configurations whose state layers ride in its
buffers (Solar Open 2: delta-rule layers; Nemotron 3 Nano: Mamba-2), and
read in the OPTIMIZED HLO what the compiler made of the buffers
(``transformer.window_buffer``): their physical layout keeps the step
outermost, every in-place write to one is a dense slab of leading rows,
and nothing copies a whole buffer inside the scan.

XLA assigns a carried buffer's layout from what reads it after the scan:
with the step an axis of its own it has put that axis among a tile's
rows (a step's write then touches every tile of 1.2 GB), and with step
and layer as one leading axis it has put the BATCH outermost for the one
buffer a custom call reads (a 151 MB copy a layer group a step). These
compiles are what shows it without a chip.

The topology is described inside a fixture and every compile runs in the
test's own process; nothing here touches a backend at import. The
configurations' plans (shapes, the runner, the cache) are the benchmark's
own fixtures, unchanged.
"""

import re

import pytest

from tests.perfbench.test_aot_nemotron_h_v5e import plan as nemotron_plan  # noqa: F401
from tests.perfbench.test_aot_solar_v5e import plan as solar_plan  # noqa: F401
from tests.perfbench.test_aot_v5e import silent_cache  # noqa: F401

#: ``temp_size_in_bytes`` of the same program at the parent of the PR
#: that made the buffers step-major (the step axis among a tile's rows,
#: float32 copies of every buffer a layer a step)
TEMP_BEFORE = {"solar": 3_364_259_328, "nemotron": 1_111_995_392}

_TYPES = {"float32": "f32", "bfloat16": "bf16"}
_COMPUTATION = re.compile(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()")
_DEFINED = re.compile(
    r"\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+\[[\d,]*\])(\{[\d,]*)?\S* ([\w\-]+)\((.*)"
)


def decode_window(plan):
    import jax.numpy as jnp

    from sutro_tpu.engine.runner import ModelRunner

    ecfg, arg = plan["ecfg"], plan["arg"]
    B, MP = ecfg.decode_batch_size, ecfg.max_pages_per_seq
    traced = ModelRunner._decode_multi_jit.trace(
        plan["runner"], plan["params"], plan["cache"],
        arg((B,), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        ecfg.decode_multi_step, arg((B,), jnp.int32), 1, None,
    )
    return traced.lower(lowering_platforms=("tpu",)).compile()


def carried_buffers(plan):
    """The HLO types of the window's state buffers, e.g.
    ``bf16[792,16,24576]``."""
    import jax
    import jax.numpy as jnp

    from sutro_tpu.models import transformer

    m, ecfg = plan["mcfg"], plan["ecfg"]
    act = jnp.dtype(ecfg.activation_dtype)
    steps, B = ecfg.decode_multi_step, ecfg.decode_batch_size
    named = (("conv", m.state_conv_dim, act),) + transformer.pending_buffers(
        m, act
    )
    out = set()
    for name, width, dt in named:
        tokens = steps + (m.state_conv_len if name == "conv" else 0)
        s = jax.eval_shape(
            lambda: transformer.window_buffer(
                tokens, m.num_state_layers, B, width, dt
            )
        )
        out.add(
            f"{_TYPES[str(s.dtype)]}[{','.join(str(d) for d in s.shape)}]"
        )
    return out


def what_the_compiler_made(text, buffers):
    """``(layouts, ragged, copies)`` of the carried ``buffers`` in the
    optimized HLO ``text``: every physical layout one of them was given;
    each ``dynamic-update-slice`` into one whose update is narrower than
    the buffer along another axis than the leading one; each ``copy`` of
    a whole one outside the entry computation (the scan's bodies and the
    fusions they call)."""
    layouts, ragged, copies = set(), [], []
    for comp in _COMPUTATION.split(text):
        head, _, body = comp.partition("\n")
        shapes = {}
        for line in body.splitlines():
            m = _DEFINED.match(line)
            if m:
                shapes[m.group(1)] = m.group(2)
        for line in body.splitlines():
            m = _DEFINED.match(line)
            if not m or m.group(2) not in buffers:
                continue
            name, shape, layout, op, operands = m.groups()
            if layout:
                layouts.add(layout + "}")
            if op == "dynamic-update-slice":
                update = shapes.get(operands.split(", ")[1].strip(" )"), "?")
                if update.split(",")[1:] != shape.split(",")[1:]:
                    ragged.append(f"{shape} <- {update}")
            if op in ("copy", "copy-start") and not head.startswith("ENTRY"):
                copies.append(line.strip()[:120])
    return layouts, ragged, copies


def test_the_reader_sees_what_it_is_there_to_see():
    """On a text with each fault in it: a buffer laid batch-major, an
    update of one row of a tile, a copy of a whole buffer in a body."""
    text = """HloModule m

%fused.1 (p0: bf16[48,16,128], p1: bf16[6,1,128]) -> bf16[48,16,128] {
  %p0 = bf16[48,16,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[6,1,128]{2,1,0:T(8,128)(2,1)} parameter(1)
  %c = s32[]{:T(128)} constant(0)
  ROOT %dus = bf16[48,16,128]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%p0, %p1, %c, %c, %c)
}

%body.2 (t: (bf16[48,16,128])) -> (bf16[48,16,128]) {
  %t = (bf16[48,16,128]{2,0,1:T(8,128)(2,1)}) parameter(0)
  %g = bf16[48,16,128]{2,0,1:T(8,128)(2,1)} get-tuple-element(%t), index=0
  %copy.7 = bf16[48,16,128]{2,1,0:T(8,128)(2,1)} copy(%g)
  ROOT %r = (bf16[48,16,128]{2,1,0:T(8,128)(2,1)}) tuple(%copy.7)
}

ENTRY %main.3 (a: bf16[48,16,128]) -> bf16[48,16,128] {
  %a = bf16[48,16,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[48,16,128]{2,1,0:T(8,128)(2,1)} copy(%a)
}
"""
    layouts, ragged, copies = what_the_compiler_made(
        text, {"bf16[48,16,128]"}
    )
    assert layouts == {"{2,1,0}", "{2,0,1}"}   # the second: batch outermost
    assert ragged == ["bf16[48,16,128] <- bf16[6,1,128]"]
    assert len(copies) == 1 and "%copy.7" in copies[0]


@pytest.mark.parametrize("which", ["solar", "nemotron"])
def test_the_windows_buffers_stay_step_major_and_nothing_copies_one(
    request, silent_cache, which  # noqa: F811
):
    plan = request.getfixturevalue(f"{which}_plan")
    compiled = decode_window(plan)
    buffers = carried_buffers(plan)
    assert len(buffers) >= 3, buffers
    text = compiled.as_text()
    layouts, ragged, copies = what_the_compiler_made(text, buffers)
    # each buffer is there, and in the scan (a while carries it)
    for b in buffers:
        assert any(b in line for line in text.splitlines() if " while(" in line), b
    # the leading axis (step, layer, batch tile) is physically outermost,
    # so a tile is rows of ONE step (a buffer narrower than a tile's 128
    # lanes, a Mamba-2 head's dt, may have its rows for lanes: {1,2,0})
    assert layouts and all(lay.endswith(",0}") for lay in layouts), layouts
    assert ragged == []
    assert copies == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(which, "decode window temp bytes", temp)
    assert plan["runner"]._window_state_bytes() > 0
    assert temp < TEMP_BEFORE[which], temp
