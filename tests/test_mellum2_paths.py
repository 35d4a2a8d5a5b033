"""What the cell of a model that keeps K/V a pool a kind BYPASSES is still
held: the paths that cannot carry a window pool fall back to prefilling
again and count it, or work through the identity map, or refuse with a
message that names the mechanism; and the small repairs the model
brought (the loader's refusal, the walk's list of refusals, the grouped
product's choice by shape)."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import mellum2_moe
from sutro_tpu import telemetry
from sutro_tpu.engine.config import EngineConfig
from sutro_tpu.engine.kvtier import KVTierPool
from sutro_tpu.engine.prefixstore import PrefixStore
from sutro_tpu.engine.runner import ModelRunner
from sutro_tpu.engine.scheduler import ContinuousBatcher, GenRequest, _Slot
from sutro_tpu.engine.tokenizer import ByteTokenizer
from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import lowering, moe

MCFG = MODEL_CONFIGS["tiny-mellum2"]
KEYS = json.loads(
    (Path(correctness.__file__).parent
     / "rehearsal/configs/tiny-mellum2-cpu.json").read_text()
)
PS, MP, B = 4, 24, 4
TOK = ByteTokenizer(vocab_size=MCFG.vocab_size)


def engine(**kw):
    base = dict(
        kv_page_size=PS, max_pages_per_seq=MP, decode_batch_size=B,
        max_model_len=PS * MP, use_pallas=False, param_dtype="float32",
        activation_dtype="float32", prefill_chunk=64, seed=9,
        decode_multi_step=4,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def one_pool_id():
    """The trivial setting: a runner given its pool's size."""
    return ModelRunner(MCFG, engine(), num_pages=1 + B * MP)


@pytest.fixture(scope="module")
def two_pools(one_pool_id):
    r = ModelRunner(
        MCFG, engine(), params=one_pool_id.params, num_pages=1 + B * MP,
        window_pages=1 + B * one_pool_id.window_span,
    )
    assert r.pools.window is not None
    return r


@pytest.fixture(autouse=True)
def _telemetry_on():
    before = telemetry.ENABLED
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(before)


def reqs(prompts, new=8):
    return [
        GenRequest(row_id=i, prompt_ids=np.array(TOK.encode(p), np.int32),
                   max_new_tokens=new, temperature=0.0)
        for i, p in enumerate(prompts)
    ]


def run(b, rs):
    out = {}
    b.run(rs, on_result=lambda r: out.__setitem__(r.row_id, r))
    return {i: list(r.token_ids) for i, r in sorted(out.items())}


def fallback(reason):
    series = telemetry.REGISTRY.collect().get(
        "sutro_state_fallback_prefill_tokens_total", {}
    ).get("series", {})
    return sum(v for k, v in series.items() if reason in str(k))


SHELL = "one shared shell of twenty-odd bytes, then: "
PROMPTS = [SHELL + t for t in ("alpha", "beta beta", "gamma gamma gamma")]


def test_through_the_scheduler_greedy_tokens_are_the_references(two_pools):
    got = run(ContinuousBatcher(two_pools, stop_ids=[]), reqs(PROMPTS, new=14))
    for i, p in enumerate(PROMPTS):
        ids = TOK.encode(p) + got[i]
        want = np.asarray(mellum2_moe.logits_at(
            KEYS, two_pools.params, ids[:-1],
            list(range(len(ids) - 15, len(ids) - 1)),
        ))
        assert got[i] == [int(t) for t in want.argmax(-1)]
    assert two_pools.pools.window.released_total > 0


def test_rows_that_share_a_prefix_prefill_it_again_and_say_so(two_pools):
    cold = run(ContinuousBatcher(two_pools, stop_ids=[]), reqs(PROMPTS[:1]))
    before = fallback("prefix_without_window_pages")
    b = ContinuousBatcher(two_pools, stop_ids=[], prefix_store=PrefixStore(PS))
    assert b._prefix_store is None        # a shared page has no window page
    got = run(b, reqs(PROMPTS))
    shared = (len(TOK.encode(SHELL)) // PS) * PS
    assert shared >= PS
    assert fallback("prefix_without_window_pages") - before == 2 * shared
    assert got[0] == cold[0]
    assert b.prefill_tokens == sum(len(TOK.encode(p)) for p in PROMPTS)


def test_at_the_trivial_setting_a_shared_prefix_works_through_the_map(
    one_pool_id, two_pools
):
    """A page id carries both kinds, so the rows' shared pages hold the
    window layers' K/V too: the prefix is prefilled once, and the tokens
    are those of rows that prefilled their own."""
    own = run(ContinuousBatcher(two_pools, stop_ids=[]), reqs(PROMPTS))
    b = ContinuousBatcher(one_pool_id, stop_ids=[])
    got = run(b, reqs(PROMPTS))
    assert got == own
    assert b.prefill_tokens < sum(len(TOK.encode(p)) for p in PROMPTS)


@pytest.mark.parametrize("which", ["two_pools", "one_pool_id"])
def test_a_row_that_would_hibernate_regenerates_and_says_so(which, request):
    runner = request.getfixturevalue(which)
    tier = KVTierPool(page_size=PS, host_pages=64)
    b = ContinuousBatcher(runner, stop_ids=[], kv_tier=tier)
    assert b._kv_tier is None and not b._can_hibernate
    req = reqs(["a row that is preempted in mid-sequence"], new=4)[0]
    i, pages, _ = b._reserve(req, types.SimpleNamespace(prefix=None))
    b.slots[i] = _Slot(req=req, pages=list(pages), pos=21, last_token=1,
                       job=None, shared_n=0)
    before = fallback("hibernate_without_window_pages")
    assert b._hibernate_slot(i) is False      # the caller suspends plainly
    assert fallback("hibernate_without_window_pages") - before == 21
    with pytest.raises(ValueError, match="a pool a kind"):
        runner.read_pages([1])
    with pytest.raises(ValueError, match="a pool a kind"):
        runner.write_pages([1], {})
    b.slots[i] = None
    b._unreserve(i, pages)
    if runner.pools.window is not None:
        assert runner.pools.window.budget_free == runner.pools.window.total


def test_tensor_parallel_works_through_the_identity_map(two_pools):
    """A mesh runs the mechanism at its trivial setting: both pools
    sharded over the KV heads, nothing released, the same tokens."""
    if jax.device_count() < 2:
        pytest.skip("needs two host devices")
    r = ModelRunner(MCFG, engine(tp=2), num_pages=1 + B * MP)
    assert r.mesh is not None and r.pools.window is None
    assert r.cache.wk_pages.sharding.spec == r.cache.k_pages.sharding.spec
    want = run(
        ContinuousBatcher(
            ModelRunner(MCFG, engine(), num_pages=1 + B * MP), stop_ids=[]
        ),
        reqs(PROMPTS[1:]),
    )
    assert run(ContinuousBatcher(r, stop_ids=[]), reqs(PROMPTS[1:])) == want


def test_what_cannot_carry_a_window_pool_refuses_by_name():
    with pytest.raises(NotImplementedError, match="no int8 scale pools"):
        ModelRunner(MCFG, engine(kv_quantize="int8"), num_pages=9)
    with pytest.raises(NotImplementedError, match="several kinds"):
        ModelRunner(MCFG, engine(quantize="int8"), num_pages=9)


def test_a_mellum_checkpoint_is_refused_not_loaded_as_qwen3_moe():
    from sutro_tpu.engine import weights

    with pytest.raises(NotImplementedError, match="rope_parameters"):
        weights._load_mixed(MCFG, lambda *a, **k: None, jnp.float32)


def test_the_walk_says_what_it_supports_and_refuses_the_rest():
    import dataclasses

    transformer._check_mixed(MCFG)            # windows by kind, YaRN: fine
    bad = dataclasses.replace(MCFG, sliding_pattern="alternate")
    with pytest.raises(NotImplementedError, match="list the layers' kinds"):
        transformer._check_mixed(bad)
    with pytest.raises(ValueError, match="need a sliding_window"):
        transformer._check_mixed(dataclasses.replace(MCFG, sliding_window=0))
    # a scanned model cannot mix a local theta with YaRN: the message
    # says how to list the layers instead
    scanned = dataclasses.replace(
        MODEL_CONFIGS["tiny-oss"], rope_scaling_factor=4.0,
        rope_original_max=16, local_rope_theta=1e4,
    )
    x = jnp.zeros((1, 2, 2, 32))
    with pytest.raises(NotImplementedError, match="layer_types"):
        transformer.apply_rope(x, jnp.zeros((1, 2), jnp.int32), 1e4, scanned)


def test_the_windows_of_every_preset_come_from_one_per_layer_list():
    assert MCFG.window_array() == (8, 8, 8, 0)
    assert MODEL_CONFIGS["mellum2-12b-a2.5b"].window_array() == (
        (1024, 1024, 1024, 0) * 7
    )
    assert MODEL_CONFIGS["tiny-oss"].window_array() == (8, 0)
    assert MODEL_CONFIGS["gemma3-4b"].window_array()[:6] == (1024,) * 5 + (0,)
    assert MODEL_CONFIGS["qwen3-4b"].window_array() == (0,) * 36


def test_the_published_28_layers_are_one_group_of_seven_periods():
    big = MODEL_CONFIGS["mellum2-12b-a2.5b"]
    assert transformer.layer_groups(big) == [(0, 4, 7)]
    assert transformer.layer_groups(MODEL_CONFIGS["mellum2-12b-a2.5b-l8"]) == [
        (0, 4, 2)
    ]
    assert (big.num_window_layers, big.num_attn_layers) == (21, 7)
    assert not big.homogeneous and MODEL_CONFIGS["qwen3-4b"].homogeneous


def _stacked_experts(H, F, L=3, E=16, N=10):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (1, N, H))
    router = jax.random.normal(ks[1], (H, E))
    wg, wu = (jax.random.normal(k, (L, E, H, F)) * 0.1 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (L, E, F, H)) * 0.1
    return x, router, wg, wu, wd


@pytest.mark.parametrize("use_pallas", [True, False])
def test_the_expert_stack_is_read_in_place_whatever_the_kernel_switch(
    use_pallas, monkeypatch
):
    """``moe_mlp(layer=)`` under the kernel and under ``ragged_dot``
    alike: no slice of one layer's experts (a copy of them a step), the
    numbers of the sliced layer, and the grouped product over the flat
    stack: indexed from ``layer * E`` by the kernel, the other layers'
    groups empty for ``ragged_dot``."""
    from tests.test_prefix_split import _force_interpret

    _force_interpret(monkeypatch)  # the kernels interpreted, on the CPU
    L, E, H, F = 3, 16, 128, 256
    x, router, wg, wu, wd = _stacked_experts(H, F, L, E)

    def f(layer=1):
        return moe.moe_mlp(
            x, router, wg, wu, wd, top_k=4, use_pallas=use_pallas,
            layer=jnp.int32(layer),
        )

    before = lowering.grouped_matmul_counts()
    sliced = moe.moe_mlp(x, router, wg[1], wu[1], wd[1], top_k=4)
    np.testing.assert_allclose(f(), sliced, rtol=2e-4, atol=2e-4)
    assert not np.allclose(f(2), sliced, atol=1e-3)  # the index is read
    text = str(jax.make_jaxpr(f)())
    assert f"f32[{L * E},{H},{F}]" in text
    assert f"f32[{E},{H},{F}]" not in text     # no layer's experts apart
    assert ("pallas_call" in text) == use_pallas
    assert ("ragged_dot" in text) == (not use_pallas)
    # ragged_dot alone is handed a group a stacked expert
    assert (f"i32[{L * E}]" in text) == (not use_pallas)
    after = lowering.grouped_matmul_counts()
    assert (after["interpreted"] > before["interpreted"]) == use_pallas
    assert after["lowered"] == before["lowered"]
    assert after["reference"] == before["reference"]


def test_the_grouped_product_has_one_gate_the_switch_and_the_shapes():
    """What keeps a cell where it is: ``use_pallas=False`` traces
    ``ragged_dot`` and no kernel (the LFM2 cell's file says so), and
    under ``use_pallas=True`` shapes off the 128-lane grid fall back
    and are counted as ``reference``; the three attention / K-V keys of
    ``snapshot()`` never see the routed product."""
    x, router, wg, wu, wd = _stacked_experts(32, 24)

    def f(use_pallas):
        return moe.moe_mlp(
            x, router, wg, wu, wd, top_k=4, use_pallas=use_pallas,
            layer=jnp.int32(1),
        )

    snap, before = lowering.snapshot(), lowering.grouped_matmul_counts()
    off = str(jax.make_jaxpr(lambda: f(False))())
    assert "ragged_dot" in off and "pallas_call" not in off
    assert lowering.grouped_matmul_counts() == before
    fell_back = str(jax.make_jaxpr(lambda: f(True))())
    assert "ragged_dot" in fell_back and "pallas_call" not in fell_back
    after = lowering.grouped_matmul_counts()
    assert after["reference"] == before["reference"] + 3   # gate, up, down
    assert after["lowered"] == before["lowered"]
    assert after["interpreted"] == before["interpreted"]
    np.testing.assert_allclose(f(True), f(False), rtol=1e-6, atol=1e-6)
    assert lowering.snapshot() == snap


def test_a_mesh_that_shards_the_operands_keeps_the_routed_product_on_ragged_dot():
    """``_mlp`` is handed the runner's ``kernel_mesh``: where a mesh
    shards the experts outside a ``shard_map`` the call stays on
    ``ragged_dot`` (XLA cannot partition a Mosaic call), decided by what
    it is handed and by no model's name."""
    E, H, F = 16, 128, 128
    x, router, wg, wu, wd = _stacked_experts(H, F, 2, E, N=12)  # top-2
    lp = {"router": router, "we_gate": wg[0], "we_up": wu[0], "we_down": wd[0]}

    def text(kernel_mesh):
        return str(jax.make_jaxpr(lambda: transformer._mlp(
            MODEL_CONFIGS["tiny-moe"], lp, x, use_pallas=True,
            kernel_mesh=kernel_mesh,
        ))())

    sharded, whole = text(object()), text(None)
    assert "ragged_dot" in sharded and "pallas_call" not in sharded
    assert "pallas_call" in whole and "ragged_dot" not in whole
