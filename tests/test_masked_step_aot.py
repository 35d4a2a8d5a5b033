"""Compile for a described v5e (no chip attached) the masked single step
of the dense 4B cell (``perfbench/configs/qwen3-4b-v5e1.json``: batch 64,
a 151,936-token vocabulary, ``allowed_packed`` given, as the classify
job's every decode step) and read in the OPTIMIZED HLO where the
sampler's exact head went: the ``TopK`` custom call over [64, 151,936]
lies inside one branch of a ``conditional`` of the entry computation and
nowhere else, and that branch is not the one the device takes when every
temperature is 0 (``ops/sampling.py``: a batch whose rows are all greedy
is sampled by its argmax alone). What the step costs on the chip with
and without the head is ``perfbench/tools/part_table.py``'s to say.

The topology is described inside a fixture and the compile runs in the
test's own process; nothing here touches a backend at import.
"""

import functools
import json
import re
from pathlib import Path

import pytest

from tests.perfbench.test_aot_v5e import silent_cache  # noqa: F401
from tests.test_window_buffers_aot import _COMPUTATION

REPO = Path(__file__).resolve().parents[1]
CFG = json.loads((REPO / "perfbench/configs/qwen3-4b-v5e1.json").read_text())
#: pages of the pool the compile is given: the program is the same at any
PAGES = 400

_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=(%[\w.\-]+)"
)
_CALLED_LIST = re.compile(
    r"(?:branch_computations|called_computations)=\{([^}]*)\}"
)
_CONDITIONAL = re.compile(
    r"(%[\w.\-]+) = .*? conditional\((%[\w.\-]+), .*?"
    r"branch_computations=\{([^}]*)\}"
)
#: what only the stochastic side may hold: the head, and the draw's bits
_HEAD = ('custom_call_target="TopK"', " sort(", "rng-bit-generator(",
         'custom_call_target="ApproxTopK"', 'custom_call_target="PartialReduce"')


def computations(text):
    """``{name: body}`` of an HLO module's computations, and the entry's
    name."""
    out, entry = {}, None
    for comp in _COMPUTATION.split(text):
        head = comp.split("\n", 1)[0]
        is_entry = head.startswith("ENTRY ")
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", head)
        if not m:
            continue
        out[m.group(1)] = comp
        if is_entry:
            entry = m.group(1)
    return out, entry


def callees(body):
    names = set(_CALLED.findall(body))
    for group in _CALLED_LIST.findall(body):
        names |= {n.strip() for n in group.split(",") if n.strip()}
    return names


def reachable(comps, root, stop=()):
    """``root`` and every computation it calls, those in ``stop`` and
    what only they call left out."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name in stop or name not in comps:
            continue
        seen.add(name)
        todo.extend(callees(comps[name]))
    return seen


def holds_head(comps, names):
    return sorted(
        (n, h) for n in names for h in _HEAD if h in comps[n]
    )


def test_the_reader_sees_what_it_is_there_to_see():
    """On a text with the head in each place: the entry computation, the
    branch a greedy batch takes, the other branch."""
    text = """HloModule m

%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %gt = pred[] compare(%a, %b), direction=GT
}

%fused.head (p: f32[8,64]) -> f32[8,4] {
  %p = f32[8,64] parameter(0)
  %cc = (f32[8,4], s32[8,4]) custom-call(%p), custom_call_target="TopK", called_computations={%cmp}
  ROOT %g = f32[8,4] get-tuple-element(%cc), index=0
}

%drawn.1 (t: (f32[8,64])) -> (f32[8,4]) {
  %t = (f32[8,64]) parameter(0)
  %x = f32[8,64] get-tuple-element(%t), index=0
  %f = f32[8,4] fusion(%x), kind=kLoop, calls=%fused.head
  ROOT %r = (f32[8,4]) tuple(%f)
}

%greedy.2 (t: (f32[8,64])) -> (f32[8,4]) {
  %t = (f32[8,64]) parameter(0)
  %x = f32[8,64] get-tuple-element(%t), index=0
  %s = f32[8,4] slice(%x), slice={[0:8], [0:4]}
  ROOT %r = (f32[8,4]) tuple(%s)
}

ENTRY %main.3 (a: f32[8,64], i: s32[]) -> (f32[8,4]) {
  %a = f32[8,64] parameter(0)
  %i = s32[] parameter(1)
  %ta = (f32[8,64]) tuple(%a)
  ROOT %conditional = (f32[8,4]) conditional(%i, %ta, %ta), branch_computations={%drawn.1, %greedy.2}
}
"""
    comps, entry = computations(text)
    assert entry == "%main.3" and len(comps) == 5
    (cond,) = _CONDITIONAL.findall(comps[entry])
    branches = [b.strip() for b in cond[2].split(",")]
    assert branches == ["%drawn.1", "%greedy.2"]
    assert not holds_head(comps, reachable(comps, entry, stop=branches))
    assert holds_head(comps, reachable(comps, "%drawn.1")) == [
        ("%fused.head", 'custom_call_target="TopK"')
    ]
    assert not holds_head(comps, reachable(comps, "%greedy.2"))
    # and the head in the entry computation is seen there
    flat = text.replace("conditional(%i, %ta, %ta), branch_computations="
                        "{%drawn.1, %greedy.2}", "fusion(%a), kind=kLoop, "
                        "calls=%fused.head")
    comps, entry = computations(flat)
    assert holds_head(comps, reachable(comps, entry))


@pytest.fixture(scope="module")
def masked_step(silent_cache):  # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sutro_tpu.engine.config import EngineConfig
    from sutro_tpu.engine.kvcache import KVCache
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1),
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    ecfg = EngineConfig(**CFG["engine"])
    mcfg = MODEL_CONFIGS[CFG["engine_key"]]
    dtype = jnp.dtype(ecfg.param_dtype)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = jax.tree.map(
        lambda s: arg(s.shape, s.dtype),
        jax.eval_shape(
            functools.partial(transformer.init_params, mcfg, dtype=dtype),
            jax.random.PRNGKey(0),
        ),
    )
    r = object.__new__(ModelRunner)
    r.mcfg, r.ecfg, r.mesh = mcfg, ecfg, None
    r.sp = r.pp = 1
    r.ep_mesh = r.kernel_mesh = None
    r.use_pallas = True
    B, MP, V = ecfg.decode_batch_size, ecfg.max_pages_per_seq, mcfg.vocab_size
    pool = arg(
        (mcfg.num_layers, PAGES, ecfg.kv_page_size,
         mcfg.num_kv_heads * mcfg.head_dim), dtype,
    )
    # ids, past_len, page_table, rng, temperature, top_p, top_k, the
    # bit-packed masks; no row seeds, penalties or split prefix: the
    # classify job's step
    traced = ModelRunner._decode_jit.trace(
        r, params, KVCache(k_pages=pool, v_pages=pool),
        arg((B, 1), jnp.int32), arg((B,), jnp.int32), arg((B, MP), jnp.int32),
        arg((2,), jnp.uint32), arg((B,), jnp.float32), arg((B,), jnp.float32),
        arg((B,), jnp.int32), arg((B, (V + 7) // 8), jnp.uint8),
        None, None, None,
    )
    return traced.lower(lowering_platforms=("tpu",)).compile().as_text()


def test_the_exact_head_lies_in_the_branch_a_greedy_batch_does_not_take(
    masked_step,
):
    comps, entry = computations(masked_step)
    assert entry is not None
    with_head = {n for n, _h in holds_head(comps, comps)}
    assert any('custom_call_target="TopK"' in comps[n] for n in with_head)
    # ONE conditional of the entry computation has the head under it
    conds = [
        (name, index, [b.strip() for b in branches.split(",")])
        for name, index, branches in _CONDITIONAL.findall(comps[entry])
    ]
    conds = [
        c for c in conds
        if any(reachable(comps, b) & with_head for b in c[2])
    ]
    assert len(conds) == 1, conds
    _name, index, (drawn, greedy) = conds[0]
    # the head is nowhere in what the entry computation runs itself ...
    assert not holds_head(
        comps, reachable(comps, entry, stop=(drawn, greedy))
    )
    # ... all of it is in the first branch, none in the second ...
    assert with_head <= reachable(comps, drawn)
    assert not holds_head(comps, reachable(comps, greedy))
    assert " reduce(" in "".join(
        comps[n] for n in reachable(comps, greedy)
    )   # the argmax
    # ... and the second is the one taken when every temperature is 0:
    # the branch index is the predicate itself (false 0, true 1), an
    # and-reduction of ``temperature <= 0`` over the step's own operand
    entry_lines = {
        m.group(1): line for line in comps[entry].splitlines()
        if (m := re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", line))
    }
    convert = entry_lines[index]
    assert re.search(r"= s32\[\]\S* convert\(", convert), convert
    pred = entry_lines[re.search(r"convert\((%[\w.\-]+)\)", convert).group(1)]
    assert re.search(r"= pred\[\]\S* fusion\(%temperature", pred), pred
    fused = comps[_CALLED.search(pred).group(1)]
    assert "direction=LE" in fused and " reduce(" in fused, fused
    assert "constant(0)" in fused
