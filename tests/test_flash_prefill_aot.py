"""The flash prefill body at the cells' real shapes, with no chip
attached (``ops/pallas_flash.py``; after ``tests/test_paged_chunk_aot.py``):

- a GQA call compiled for a described v5e at the shapes of the two cells
  that lean on it and at the tightest group: ``[1, 8192]`` at Laguna's 72
  heads (window 512) and 48 (full), ``[1, 4096]`` at Mellum 2's 32 over 4
  (window 1,024 and full), ``[1, 2048]`` at 32 over 2 (``MAX_GROUP``).
  Mosaic refuses a kernel whose scoped VMEM is over what the call asks
  (``GQA_VMEM_BYTES``), so a compile that passes holds the tile
  ``gqa_tiles`` chose under it; the count of forms says which walk the
  call took;
- a LATENT caller's program (``ops/attention.latent_flash`` at JoyAI's,
  GLM-5's and Xing's shapes) is the one the parent of PR 62 traced: the
  jaxpr of the whole call, kernel body and index maps included, letter
  for letter;
- a MODEL's own forward, traced: Laguna's window layers hand the body
  their window before tracing (72 heads, ``walk=window``) and its full
  layers the 0 beside it (48, ``walk=causal``), Mellum 2 likewise, a
  homogeneous scan's layers a runtime scalar (``walk=dynamic``).

The topology is described inside a fixture and the compiles run in the
test's own process; nothing here touches a backend at import.
"""

import hashlib

import pytest

from tests.perfbench.test_aot_v5e import silent_cache  # noqa: F401

#: name: (T, query heads, K/V heads, the layer kind's window or None for
#: a scan's layer, whose window is a runtime scalar; the form it takes)
GQA = {
    "laguna-window": (8192, 72, 8, 512, "tile=512x512 walk=window"),
    "laguna-full": (8192, 48, 8, 0, "tile=512x512 walk=causal"),
    "mellum2-window": (4096, 32, 4, 1024, "tile=512x512 walk=window"),
    "mellum2-full": (4096, 32, 4, 0, "tile=512x512 walk=causal"),
    "group-16": (2048, 32, 2, None, "tile=512x512 walk=dynamic"),
}


@pytest.fixture(scope="module")
def one_chip(silent_cache):  # noqa: F811
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1),
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(GQA))
def test_a_gqa_call_compiles_for_a_v5e_under_the_vmem_it_asks(one_chip, name):
    import jax
    import jax.numpy as jnp

    from sutro_tpu.ops import lowering, pallas_flash

    T, NH, KVH, window, form = GQA[name]
    bf = jnp.bfloat16

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, k = arg((1, T, NH, 128), bf), arg((1, T, KVH, 128), bf)
    vl, win = arg((1,), jnp.int32), arg((), jnp.int32)

    def call(q, k, v, vl, win):
        if window is None:
            return pallas_flash.flash_prefill(q, k, v, valid_len=vl, window=win)
        return pallas_flash.flash_prefill(
            q, k, v, valid_len=vl, live_window=window
        )

    before = lowering.flash_prefill_counts()
    compiled = jax.jit(call).trace(q, k, k, vl, win).lower(
        lowering_platforms=("tpu",)
    ).compile()
    calls = [
        line for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert len(calls) == 1, "one Mosaic call in the program"
    grew = {
        key for key, n in lowering.flash_prefill_counts().items()
        if n > before.get(key, 0)
    }
    assert grew == {f"flash_prefill@{NH} {form} operands=bfloat16"}
    side = pallas_flash.gqa_tiles(T, NH // KVH, 128, 128)[0]
    took = pallas_flash.gqa_vmem_bytes(NH // KVH, side, side, 128, 128, 2)
    assert took <= pallas_flash.GQA_VMEM_BUDGET < pallas_flash.GQA_VMEM_BYTES


#: name: (T, heads, Dq, Dv, block, under a selection; sha256 of the call's
#: jaxpr text at commit 9496ed6, the parent of PR 62, first 16 digits).
#: The lowered StableHLO carries the kernel as bytes with the source's
#: line numbers in them, so its hash moves with any edit of the file
#: (parent: 1cd650fa5c2c03b5 / 79fa6c0ed263efd7 / 3c475159c0502984 /
#: 0cf24925aebdf90a); the jaxpr is the program without them
LATENT = {
    "joyai": (4096, 32, 192, 128, 1024, False, "71f5fc04e1562cf8"),
    "glm5-keep": (8192, 64, 256, 256, 1024, True, "9c94d954a2f94a4e"),
    "xing": (2048, 32, 192, 128, 1024, False, "8792b5e06966b223"),
    "glm5-keep-512": (1536, 64, 256, 256, 512, True, "885086d80efdd3bd"),
}


@pytest.mark.parametrize("name", sorted(LATENT))
def test_a_latent_callers_program_is_the_one_it_was(name):
    import jax
    import jax.numpy as jnp

    from sutro_tpu.ops import attention, lowering

    T, NH, Dq, Dv, block, keep, parent = LATENT[name]
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct((1, T, NH, Dq), bf)] * 2
    args.append(jax.ShapeDtypeStruct((1, T, NH, Dv), bf))
    if keep:
        args.append(jax.ShapeDtypeStruct((1, T, T), jnp.int8))

    def call(q, k, v, keep=None):
        return attention.latent_flash(
            q, k, v, scale=Dq ** -0.5, block=block, keep=keep
        )

    before = lowering.flash_prefill_counts()
    text = str(jax.make_jaxpr(call)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == parent
    # its own blocks, no ``valid_len``, no count among the GQA forms
    assert lowering.flash_prefill_counts() == before


#: preset: (T, the forms its whole-prompt prefill traces). A kind's
#: window and the full layers' 0 beside it are known before tracing; a
#: homogeneous scan hands every layer its window as it runs
MODELS = {
    "laguna-s-2.1-l9-ep8": (1024, {
        "flash_prefill@72 tile=512x512 walk=window operands=bfloat16",
        "flash_prefill@48 tile=512x512 walk=causal operands=bfloat16",
    }),
    "mellum2-12b-a2.5b-l8": (2048, {
        "flash_prefill@32 tile=512x512 walk=window operands=bfloat16",
        "flash_prefill@32 tile=512x512 walk=causal operands=bfloat16",
    }),
    "qwen3-4b": (512, {
        "flash_prefill@32 tile=512x512 walk=dynamic operands=bfloat16",
    }),
}


@pytest.mark.parametrize("preset", sorted(MODELS))
def test_a_models_prefill_takes_the_walk_its_layers_know(preset):
    """The model's own forward, traced (nothing compiled, nothing run):
    which form each head count of its layers hands the flash body."""
    import functools

    import jax
    import jax.numpy as jnp

    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS
    from sutro_tpu.ops import lowering

    T, forms = MODELS[preset]
    mcfg = MODEL_CONFIGS[preset]
    params = jax.eval_shape(
        functools.partial(transformer.init_params, mcfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0),
    )
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32)
    before = lowering.flash_prefill_counts()
    jax.eval_shape(
        functools.partial(transformer.forward, mcfg, use_pallas=True),
        params, ids, ids, jax.ShapeDtypeStruct((1,), jnp.int32),
    )
    grew = {
        key for key, n in lowering.flash_prefill_counts().items()
        if n > before.get(key, 0)
    }
    assert grew == forms
