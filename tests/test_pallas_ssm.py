"""ops/pallas_ssm.py: a decode step's read of the committed Mamba-2 state,
the kernel (interpreted here; its compiles for the chip ride in the
ahead-of-time compiles of tests/perfbench/test_aot_nemotron_h_v5e.py)
against the XLA expression of ``transformer.ssd_pending``, which stays
the fallback and is what the kernel is held to; and the static gate that
chooses between them.

The tolerance is the reorder of one float32 sum and nothing more: both
paths form the same ``N`` float32 products a channel (the state's stored
value times a float32 ``C``: exact in float32 where the state is bf16 and
``C`` is split into three bf16 terms) and add them in different orders,
so each is within ``(N - 1) u sum|products|`` of the true sum (``u`` =
2**-24) and they are within twice that of each other. ``C`` rounded to
ONE bf16 term is outside that worst-case bound in most channels (the
last test), so the bound would catch it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.models import transformer
from sutro_tpu.models.configs import MODEL_CONFIGS
from sutro_tpu.ops import lowering, pallas_ssm

U = 2.0 ** -24


def config(G, N, I, head_dim=64):
    return dataclasses.replace(
        MODEL_CONFIGS["tiny-granite"], mamba_heads=I // head_dim,
        mamba_head_dim=head_dim, mamba_state=N, mamba_groups=G,
    )


def operands(G, N, I, NS, B, T, W, dtype, layers=2, seed=0):
    """A stacked pool and a chunk's operands; rows hold distinct slots
    in no order (slot 0 is the garbage slot), the first row is fresh."""
    cfg = config(G, N, I)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    ssm = jax.random.normal(ks[0], (layers, NS, N, I), jnp.float32).astype(dtype)
    rng = np.random.default_rng(seed)
    slots = 1 + rng.permutation(NS - 1)[:B]
    fresh = np.zeros((B,), bool)
    fresh[0] = True
    x = jax.random.normal(ks[1], (B, W, I), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, W, cfg.mamba_heads)))
    Bm = jax.random.normal(ks[3], (B, W, G * N), jnp.float32)
    Cq = jax.random.normal(ks[4], (B, T, G * N), jnp.float32)
    return cfg, ssm, jnp.asarray(slots, jnp.int32), jnp.asarray(fresh), (
        x, dt, -0.3 * dt, Bm, Cq
    )


def pending(cfg, ssm, slots, fresh, chunk, q0, **kw):
    """``ssd_pending`` of every layer, the layer a TRACED index into the
    stacked pool inside ``lax.scan``, as the model's walk reads it."""

    @jax.jit
    def run(ssm, slots, fresh, chunk):
        return jax.lax.scan(
            lambda _, layer: (None, transformer.ssd_pending(
                cfg, ssm, layer, slots, fresh, *chunk, q0, **kw
            )),
            None, jnp.arange(ssm.shape[0], dtype=jnp.int32),
        )[1]

    return np.asarray(run(ssm, slots, fresh, chunk))


def reorder_bound(ssm, slots, Cq, G):
    """[L, B, T, I]: twice ``(N - 1) u sum_n |S C|`` (module docstring)."""
    S = np.abs(np.asarray(ssm.astype(jnp.float32)))[:, np.asarray(slots)]
    L, B, N, I = S.shape
    C = np.abs(np.asarray(Cq)).reshape(B, -1, G, N)
    total = np.einsum("lbngw,btgn->lbtgw", S.reshape(L, B, N, G, I // G), C)
    return 2 * (N - 1) * U * total.reshape(L, B, -1, I)


KERNEL = pallas_ssm.ssm_state_read


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel in interpret mode, in the form a test names."""

    def patch(form=None):
        monkeypatch.setattr(
            pallas_ssm, "ssm_state_read",
            functools.partial(KERNEL, interpret=True, form=form),
        )

    return patch


CASES = {
    # granite's shape cut down: one group, every channel one lane range
    "granite-bf16": dict(G=1, N=128, I=512, NS=9, B=5, T=1, W=1, q0=0,
                         dtype=jnp.bfloat16, form="mxu"),
    "granite-bf16-vpu": dict(G=1, N=128, I=512, NS=9, B=5, T=1, W=1, q0=0,
                             dtype=jnp.bfloat16, form="vpu"),
    # Nemotron's: eight groups of 512 channels at the published widths
    "nemotron-bf16": dict(G=8, N=128, I=4096, NS=7, B=4, T=1, W=1, q0=0,
                          dtype=jnp.bfloat16, form="mxu"),
    "nemotron-bf16-vpu": dict(G=8, N=128, I=4096, NS=7, B=4, T=1, W=1, q0=0,
                              dtype=jnp.bfloat16, form="vpu"),
    "nemotron-f32": dict(G=8, N=128, I=4096, NS=7, B=4, T=1, W=1, q0=0,
                         dtype=jnp.float32, form=None),
    # a window's later step: one token, five before it uncommitted
    "window-step": dict(G=8, N=128, I=1024, NS=9, B=6, T=1, W=8, q0=5,
                        dtype=jnp.bfloat16, form=None),
    # a verify chunk inside a window's buffers
    "chunk-in-window": dict(G=8, N=128, I=1024, NS=9, B=6, T=3, W=8, q0=2,
                            dtype=jnp.bfloat16, form="mxu"),
    "chunk-in-window-vpu": dict(G=2, N=128, I=256, NS=9, B=6, T=3, W=8, q0=2,
                                dtype=jnp.bfloat16, form="vpu"),
    "chunk-f32": dict(G=1, N=64, I=256, NS=9, B=6, T=4, W=4, q0=0,
                      dtype=jnp.float32, form=None),
    # the cells' pools: 1 + slots, which no block size divides; a batch
    # smaller than the slots, in no order
    "257-slots": dict(G=8, N=128, I=1024, NS=257, B=19, T=1, W=1, q0=0,
                      dtype=jnp.bfloat16, form=None),
    "129-slots": dict(G=1, N=128, I=256, NS=129, B=128, T=1, W=1, q0=0,
                      dtype=jnp.bfloat16, form=None),
    # the tiny presets': N of one sublane pack, a group of 128 channels
    "tiny-granite": dict(G=1, N=16, I=256, NS=5, B=4, T=2, W=2, q0=0,
                         dtype=jnp.float32, form=None),
    "tiny-nemotron-h": dict(G=2, N=16, I=256, NS=5, B=4, T=1, W=3, q0=2,
                            dtype=jnp.bfloat16, form=None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_xla_expression_within_a_sums_reorder(
    case, interpreted
):
    c = dict(CASES[case])
    form, q0 = c.pop("form"), c.pop("q0")
    G = c["G"]
    cfg, ssm, slots, fresh, chunk = operands(**c)
    assert pallas_ssm.state_read_supported(ssm, chunk[-1], G)
    interpreted(form)
    before = lowering.ssm_state_read_counts()
    want = pending(cfg, ssm, slots, fresh, chunk, q0)
    assert lowering.ssm_state_read_counts() == before   # off: not counted
    got = pending(cfg, ssm, slots, fresh, chunk, q0, use_pallas=True)
    after = lowering.ssm_state_read_counts()
    assert after["interpreted"] == before["interpreted"] + 1
    assert after["reference"] == before["reference"]
    bound = reorder_bound(ssm, slots, chunk[-1], G)
    assert np.all(np.abs(got - want) <= bound + 1e-30)
    # the state entered: a stale row differs from a fresh one's chunk
    own = pending(cfg, ssm, slots, jnp.ones_like(fresh), chunk, q0)
    np.testing.assert_allclose(got[:, 0], own[:, 0], rtol=1e-6, atol=1e-6)
    assert np.abs(got[:, 1:] - own[:, 1:]).max() > 1.0
    # and each layer read ITS slots
    assert np.abs(got[0] - got[1]).max() > 1.0


def test_rows_in_any_order_read_their_own_slots(interpreted):
    """The same rows permuted give the same results permuted: a row's
    block index is its slot, whatever its place in the batch."""
    interpreted()
    cfg, ssm, slots, fresh, chunk = operands(
        G=8, N=128, I=1024, NS=12, B=7, T=1, W=1, dtype=jnp.bfloat16
    )
    got = pending(cfg, ssm, slots, fresh, chunk, 0, use_pallas=True)
    perm = np.random.default_rng(3).permutation(7)
    again = pending(
        cfg, ssm, slots[perm], fresh[perm], tuple(a[perm] for a in chunk),
        0, use_pallas=True,
    )
    np.testing.assert_array_equal(again, got[:, perm])


def window_with_and_without_the_kernel(monkeypatch, runner_of, prompts, tables):
    """A model's fused window of six greedy steps under ``use_pallas``
    (every kernel interpreted) and with it off, from the same weights:
    the tokens, their log-probabilities and the state the window
    commits (read back by two more steps) agree, and only the run under
    the kernels counts ``ssm_state_read``. ``runner_of(use_pallas)``
    builds the model's runner; tests/test_granite_paths.py and
    tests/test_nemotron_h_paths.py each hold their model to it."""
    from tests.test_prefix_split import _force_interpret

    _force_interpret(monkeypatch)
    n = len(prompts)
    pad = np.zeros((2, tables.shape[1]), np.int32)
    padded = np.concatenate([tables, pad])
    lens = np.array([len(p) for p in prompts] + [0, 0], np.int32)
    zeros = np.zeros((n + 2,), np.float32)
    key = jax.random.PRNGKey(0)

    def window(use_pallas):
        r = runner_of(use_pallas)
        before = lowering.ssm_state_read_counts()
        first = np.argmax(r.prefill_batch(prompts, tables), axis=-1)
        last = np.concatenate([first, [0, 0]]).astype(np.int32)
        toks, logps = r.decode_multi(
            last, lens, padded, key, zeros, zeros + 1, 6
        )
        again, _ = r.decode_multi(                  # the commit, read back
            toks[-1], lens + 6, padded, key, zeros, zeros + 1, 2
        )
        after = lowering.ssm_state_read_counts()
        return toks, logps, again, {k: after[k] - before[k] for k in after}

    toks, logps, again, counted = window(True)
    assert counted["interpreted"] > 0
    assert counted["reference"] == counted["lowered"] == 0
    want_toks, want_logps, want_again, uncounted = window(False)
    assert not any(uncounted.values())
    np.testing.assert_array_equal(toks[:, :n], want_toks[:, :n])
    np.testing.assert_array_equal(again[:, :n], want_again[:, :n])
    np.testing.assert_allclose(
        logps[:, :n], want_logps[:, :n], rtol=0, atol=5e-4
    )


def _text(cfg, ssm, slots, fresh, chunk, **kw):
    return str(jax.make_jaxpr(lambda: transformer.ssd_pending(
        cfg, ssm, jnp.int32(1), slots, fresh, *chunk, 0, **kw
    ))())


@pytest.mark.parametrize("why,kw,shape,counted", [
    ("use_pallas off", dict(use_pallas=False), {}, 0),
    ("a mesh shards the call", dict(use_pallas=True, kernel_mesh=object()),
     {}, 1),
    ("a group's channels off the 128 grid", dict(use_pallas=True),
     dict(G=4, I=256), 1),
    ("the channels off the 128 grid", dict(use_pallas=True),
     dict(G=1, I=192), 1),
    ("N in no whole sublane pack", dict(use_pallas=True), dict(N=24), 1),
    ("a chunk wider than the kernel takes", dict(use_pallas=True),
     dict(T=pallas_ssm.MAX_TOKENS + 1, W=pallas_ssm.MAX_TOKENS + 1), 1),
    ("a pool of another dtype", dict(use_pallas=True),
     dict(dtype=jnp.float16), 1),
])
def test_what_the_gate_refuses_takes_the_xla_expression(
    why, kw, shape, counted
):
    """One gate: the engine's switch, no mesh, the static shapes. A
    refusal traces the expression and no kernel, counts ``reference``
    where ``use_pallas`` asked, and never touches ``snapshot()``."""
    base = dict(G=1, N=16, I=256, NS=5, B=3, T=1, W=1, dtype=jnp.bfloat16)
    base.update(shape)
    cfg, ssm, slots, fresh, chunk = operands(**base)
    snap, before = lowering.snapshot(), lowering.ssm_state_read_counts()
    text = _text(cfg, ssm, slots, fresh, chunk, **kw)
    assert "pallas_call" not in text and "reduce_sum" in text, why
    after = lowering.ssm_state_read_counts()
    assert after["reference"] == before["reference"] + counted
    assert after["lowered"] == before["lowered"]
    assert after["interpreted"] == before["interpreted"]
    assert lowering.snapshot() == snap
    assert set(snap) == set(lowering.KERNELS) == {
        "paged_decode", "flash_prefill", "kv_write"
    }


def test_the_kernel_is_handed_the_stack_and_never_a_layers_slice(interpreted):
    """Under the kernel no ``[NS, N, I]`` slice of the pool is formed (a
    copy of a layer's pool a layer a step as a custom call's operand);
    the expression indexes the layer itself."""
    interpreted()
    cfg, ssm, slots, fresh, chunk = operands(
        G=2, N=16, I=256, NS=5, B=3, T=1, W=1, dtype=jnp.bfloat16, layers=3
    )
    on = _text(cfg, ssm, slots, fresh, chunk, use_pallas=True)
    off = _text(cfg, ssm, slots, fresh, chunk, use_pallas=False)
    assert "pallas_call" in on and "bf16[5,16,256]" not in on
    assert "pallas_call" not in off and "bf16[5,16,256]" in off


def test_c_is_split_exactly_and_one_term_would_not_do():
    """hi + mid + lo is the float32 value to the bit; a single bf16 term
    of C lands outside the reorder bound the kernel is held to."""
    c = jax.random.normal(jax.random.PRNGKey(5), (4, 3, 256), jnp.float32)
    c = c * jnp.exp(8 * jax.random.normal(jax.random.PRNGKey(6), c.shape))
    hi, mid, lo = (t.astype(jnp.float32) for t in pallas_ssm.split_bf16(c))
    np.testing.assert_array_equal(np.asarray(hi + mid + lo), np.asarray(c))
    _, ssm, slots, _, chunk = operands(
        G=2, N=128, I=256, NS=5, B=4, T=1, W=1, dtype=jnp.bfloat16
    )
    c = chunk[-1]
    read = functools.partial(
        KERNEL, ssm, jnp.int32(1), slots, groups=2, interpret=True
    )
    want = np.asarray(read(c))
    one_term = np.asarray(read(c.astype(jnp.bfloat16).astype(jnp.float32)))
    bound = reorder_bound(ssm, slots, c, 2)[1]
    assert np.mean(np.abs(one_term - want) > bound) > 0.5
