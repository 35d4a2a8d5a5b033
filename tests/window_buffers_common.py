"""What the tests of a fused window's state buffers share, whatever the
model (``tiny-solar-kda``, ``tiny-granite``, ``tiny-nemotron-h``): a
window's step against the chunk form from the same state, a window of
eight against eight single steps, and a speculative window's commit of
an accepted prefix against the steps it accepted.

The buffers are step-major (``transformer.window_buffer``); a step reads
the window's EARLIER tokens from them and keeps its own token out of
them, so everything at and past the step (and every other layer's rows)
is filled with NaN here: a read of it, or a sum that multiplied it by
zero, would show.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from sutro_tpu.models import transformer

WINDOW = 8


def _state_mixer(mcfg):
    """(the state layers' mixer, its name among ``ModelConfig.mixers``)."""
    if mcfg.state_kind == "kda":
        return transformer.kda_mixer, "kda"
    return transformer.mamba_mixer, "mamba"


def a_windows_step_is_the_chunk_form(mcfg, params, q0: int, seed: int = 0):
    """One state layer (the LAST, so that the layer's place in the
    buffers counts) over ``q0 + 1`` tokens as a pending chunk, then the
    last of them as step ``q0`` of a fused window whose buffers hold the
    chunk's earlier tokens and NaN elsewhere: the layer's output and what
    the step leaves for the commit must be the chunk's."""
    mixer, stack = _state_mixer(mcfg)
    L = mcfg.num_state_layers
    layer = L - 1
    lp = jax.tree.map(
        lambda a: a[layer],
        params["layers"][transformer._MIXER_STACK[stack]],
    )
    B, T, K1 = 3, q0 + 1, mcfg.state_conv_len
    rng = np.random.default_rng(seed)
    f32 = jnp.float32

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, f32)

    x = normal(B, T, mcfg.hidden_size)
    past = transformer.StatePast(
        ssm=normal(L, 4, mcfg.state_rows, mcfg.state_inner, scale=0.3),
        slots=jnp.asarray([2, 1, 3], jnp.int32),
        fresh=jnp.asarray([False, True, False]),
        conv=normal(L, B, K1, mcfg.state_conv_dim),
    )
    ones = jnp.ones((B,), jnp.int32)
    y_chunk, out_chunk = mixer(
        mcfg, lp, x, valid_len=ones * T, past=past, layer=layer, pending=True
    )
    names = transformer.pending_buffers(mcfg, f32)

    def buffer(tokens, width, rows):
        """NaN but for ``rows`` [B, n, width], tokens 0..n-1 of the layer."""
        buf = transformer.window_buffer(tokens, L, B, width, f32) + jnp.nan
        for i in range(rows.shape[1]):
            every = jnp.full((L, B, width), jnp.nan, f32).at[layer].set(
                rows[:, i]
            )
            buf = transformer.window_put(buf, i, every)
        return buf

    bufs = tuple(
        buffer(WINDOW, width, out_chunk["ssm_" + name][:, :q0])
        for name, width, _ in names
    )
    conv = buffer(
        K1 + WINDOW, mcfg.state_conv_dim, out_chunk["ssm_conv"][:, : K1 + q0]
    )

    @jax.jit
    def step(conv, bufs, at):
        return mixer(
            mcfg, lp, x[:, q0:], valid_len=ones,
            past=dataclasses.replace(past, conv=conv, window=bufs + (at,)),
            layer=layer, pending=True,
        )

    y_step, out_step = step(conv, bufs, jnp.int32(q0))
    scale = float(jnp.max(jnp.abs(y_chunk)))
    assert np.all(np.isfinite(np.asarray(y_step)))
    assert float(jnp.max(jnp.abs(y_step[:, 0] - y_chunk[:, q0]))) < 1e-5 * scale
    for name, _, _ in names + (("conv", 0, 0),):
        got = np.asarray(out_step["ssm_" + name][:, -1])
        wanted = np.asarray(out_chunk["ssm_" + name][:, -1])
        assert got.shape == wanted.shape, name
        tol = 1e-5 * max(1.0, float(np.max(np.abs(wanted))))
        assert np.max(np.abs(got - wanted)) < tol, name


def eight_steps_and_a_window(runner, step, accepted=None):
    """Prefill two prompts, then (a) a greedy fused window of eight, or a
    speculative one that commits ``accepted`` of its eight, and (b) from
    the same prefill the same tokens one committed step at a time.
    Returns what both left: (log-probabilities the window reported, the
    single steps' at the same tokens, the window's state, the steps')."""
    MP = runner.ecfg.max_pages_per_seq
    prompts = [
        np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)
        for seed, n in ((8, 13), (9, 21))
    ]
    tables = np.zeros((2, MP), np.int32)
    tables[0, :5], tables[1, :5] = range(1, 6), range(6, 11)
    B = runner.ecfg.decode_batch_size
    n_rows = len(prompts)
    pad = B - n_rows
    tables_b = np.concatenate([tables, np.zeros((pad, MP), np.int32)])
    lens = np.array([len(p) for p in prompts] + [0] * pad, np.int32)
    zeros, ones = np.zeros((B,), np.float32), np.ones((B,), np.float32)

    def prefill():
        runner.pools.reset()
        first = np.argmax(runner.prefill_batch(prompts, tables), axis=-1)
        return np.concatenate([first, np.zeros((pad,), first.dtype)])

    def state(took):
        cache = runner.cache
        at = np.maximum(lens[:n_rows] + took - 1, 0) // cache.page_size
        slots = np.asarray(cache.state_slot)[tables[np.arange(n_rows), at]]
        return (
            np.asarray(cache.ssm.astype(jnp.float32))[:, slots],
            np.asarray(cache.ssm_conv.astype(jnp.float32))[slots],
        )

    last = prefill().astype(np.int32)
    if accepted is None:
        toks, logps = runner.decode_multi(
            last, lens, tables_b, jax.random.PRNGKey(0), zeros, ones, WINDOW
        )
        took = WINDOW
    else:
        toks, logps, handle = runner.decode_window(
            last, lens, tables_b, jax.random.PRNGKey(0), zeros, ones, WINDOW
        )
        took = accepted
        runner.commit_window(
            handle, np.array([accepted] * n_rows + [0] * pad, np.int32)
        )
    by_window = state(took)

    last = prefill().astype(np.int32)
    singly = []
    for i in range(took):
        logits = step(last[:n_rows], lens[:n_rows] + i, tables)
        ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        singly.append(ref[np.arange(n_rows), toks[i, :n_rows]])
        last = np.asarray(toks[i])
    return (
        np.asarray(logps)[:took, :n_rows], np.array(singly).reshape(took, n_rows),
        by_window, state(took),
    )


def close(got, wanted, tol):
    scale = max(1.0, float(np.max(np.abs(wanted)))) if wanted.size else 1.0
    return got.shape == wanted.shape and (
        not wanted.size or float(np.max(np.abs(got - wanted))) < tol * scale
    )


def a_window_is_its_steps(runner, step, tol, accepted=None):
    """The window's log-probabilities are the single steps', and the
    state it committed (all eight, or ``accepted`` of them) is theirs."""
    logps, singly, (ssm, conv), (ssm1, conv1) = eight_steps_and_a_window(
        runner, step, accepted
    )
    assert close(logps, singly, 5e-4)
    assert close(ssm, ssm1, tol)
    assert close(conv, conv1, 1e-5)
