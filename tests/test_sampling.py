"""Sampling op: greedy, top-k/top-p filters, constrained-vocabulary masks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops.sampling import apply_penalties, cumulative_logprob, sample


def _logits():
    # row 0: peaked at 3; row 1: flat-ish with max at 0
    return jnp.asarray(
        [[0.0, 1.0, 2.0, 10.0, -1.0], [3.0, 2.9, 2.8, 2.7, 2.6]], jnp.float32
    )


def test_greedy():
    toks = sample(
        _logits(), jax.random.PRNGKey(0), temperature=0.0, top_p=1.0
    )
    assert list(np.asarray(toks)) == [3, 0]


def test_top_k_one_is_greedy():
    toks = sample(
        _logits(),
        jax.random.PRNGKey(7),
        temperature=1.0,
        top_p=1.0,
        top_k=jnp.array([1, 1], jnp.int32),
    )
    assert list(np.asarray(toks)) == [3, 0]


def test_top_p_tiny_is_greedy():
    toks = sample(
        _logits(), jax.random.PRNGKey(3), temperature=1.0, top_p=1e-6
    )
    assert list(np.asarray(toks)) == [3, 0]


def test_per_row_top_k():
    # row 0: k=1 (greedy); row 1: k=0 (disabled) — both valid samples
    toks = sample(
        _logits(),
        jax.random.PRNGKey(5),
        temperature=1.0,
        top_p=1.0,
        top_k=jnp.array([1, 0], jnp.int32),
    )
    t = np.asarray(toks)
    assert t[0] == 3
    assert 0 <= t[1] < 5


def test_allowed_mask_constrains():
    allowed = jnp.asarray(
        [[False, True, False, False, False], [True, True, False, False, False]]
    )
    for seed in range(5):
        toks = sample(
            _logits(),
            jax.random.PRNGKey(seed),
            temperature=1.0,
            top_p=1.0,
            allowed=allowed,
        )
        t = np.asarray(toks)
        assert t[0] == 1
        assert t[1] in (0, 1)


def test_cumulative_logprob_matches_softmax():
    logits = _logits()
    tok = jnp.array([3, 0], jnp.int32)
    lp = np.asarray(cumulative_logprob(logits, tok))
    ref = np.log(
        np.exp(np.asarray(logits))
        / np.exp(np.asarray(logits)).sum(-1, keepdims=True)
    )
    np.testing.assert_allclose(lp, ref[[0, 1], [3, 0]], rtol=1e-4, atol=1e-6)


def test_top_k_above_cap_clamps_not_disables():
    """top_k > NUCLEUS_CAP must clamp to the cap-wide head, not fall back
    to full-vocab sampling (code-review regression)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sutro_tpu.ops.sampling import NUCLEUS_CAP, sample

    V = NUCLEUS_CAP * 4
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((1, V)), jnp.float32)
    head = set(
        np.asarray(jax.lax.top_k(logits, NUCLEUS_CAP)[1][0]).tolist()
    )
    for i in range(20):
        tok = sample(
            logits,
            jax.random.PRNGKey(i),
            temperature=jnp.float32(5.0),  # near-uniform: tail very likely
            top_p=jnp.float32(1.0),
            top_k=jnp.int32(V),  # "keep everything" — clamps to cap
        )
        assert int(tok[0]) in head


def test_apply_penalties_math():
    """Repetition/presence/frequency against a hand-computed reference.
    Repetition scope covers prompt+output (seen_rep); presence and
    frequency derive from the generated-token counts only."""
    import jax.numpy as jnp

    from sutro_tpu.ops.sampling import apply_penalties

    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0]])
    # token 2 was in the PROMPT only: repetition applies, presence/
    # frequency (generated scope) do not
    seen_rep = jnp.asarray([[True, True, True, False]])
    ids_p = jnp.asarray([[0, 1, -1]], jnp.int32)
    cnt_p = jnp.asarray([[3.0, 1.0, 0.0]])
    out = apply_penalties(
        logits, seen_rep, ids_p, cnt_p,
        presence=jnp.asarray([0.5]),
        frequency=jnp.asarray([0.25]),
        repetition=jnp.asarray([2.0]),
    )
    out = np.asarray(out[0])
    # tok0: 2.0/2 (rep) - 0.5 (presence) - 0.25*3 (freq) = -0.25
    # tok1: -1*2 (rep) - 0.5 - 0.25*1 = -2.75
    # tok2: 0.5/2 (rep only, prompt token) = 0.25
    # tok3: unseen, untouched
    np.testing.assert_allclose(out, [-0.25, -2.75, 0.25, 3.0], atol=1e-6)


def test_repetition_penalty_changes_greedy_choice():
    """Penalized logits flip the greedy argmax away from a seen token."""
    from sutro_tpu.ops.sampling import apply_penalties, sample

    B, V = 2, 16
    logits = np.zeros((B, V), np.float32)
    logits[:, 3] = 5.0   # dominant token
    logits[:, 7] = 4.0   # runner-up
    seen = np.zeros((B, V), bool)
    seen[0, 3] = True    # row 0 already emitted token 3
    ids_p = np.full((B, 4), -1, np.int32)
    cnt_p = np.zeros((B, 4), np.float32)
    ids_p[0, 0] = 3
    cnt_p[0, 0] = 1.0
    pen = apply_penalties(
        jnp.asarray(logits), jnp.asarray(seen),
        jnp.asarray(ids_p), jnp.asarray(cnt_p),
        presence=jnp.zeros(B), frequency=jnp.zeros(B),
        repetition=jnp.full(B, 3.0),
    )
    toks = np.asarray(
        sample(
            pen, jax.random.PRNGKey(0),
            temperature=np.zeros(B, np.float32),
            top_p=np.ones(B, np.float32),
        )
    )
    assert toks[0] == 7   # 5/3 < 4: penalty flips the choice
    assert toks[1] == 3   # row 1 unpenalized


def test_bfloat16_logits_supported():
    """bf16 logits sample correctly: greedy
    matches f32 for separated logits, masks still bind, and the logprob
    accumulates in f32 (no bf16 drift over the vocab)."""
    B, V = 4, 512
    rng = np.random.default_rng(0)
    logits32 = jnp.asarray(
        rng.normal(0, 2, (B, V)).astype(np.float32)
    )
    # separate the argmax by a margin far above bf16 resolution
    logits32 = logits32.at[jnp.arange(B), jnp.arange(B) + 7].add(10.0)
    logits16 = logits32.astype(jnp.bfloat16)

    g32 = sample(
        logits32, jax.random.PRNGKey(1),
        temperature=np.zeros(B, np.float32),
        top_p=np.ones(B, np.float32),
    )
    g16 = sample(
        logits16, jax.random.PRNGKey(1),
        temperature=np.zeros(B, np.float32),
        top_p=np.ones(B, np.float32),
    )
    np.testing.assert_array_equal(np.asarray(g32), np.asarray(g16))

    # constrained mask binds in bf16 too
    allowed = np.zeros((B, V), bool)
    allowed[:, 11] = True
    t16 = sample(
        logits16, jax.random.PRNGKey(2),
        temperature=np.full(B, 1.0, np.float32),
        top_p=np.ones(B, np.float32),
        allowed=jnp.asarray(allowed),
    )
    assert np.all(np.asarray(t16) == 11)

    # logprob: f32 accumulation keeps bf16 within bf16 input precision
    lp32 = np.asarray(cumulative_logprob(logits32, g32))
    lp16 = np.asarray(cumulative_logprob(logits16, g16))
    np.testing.assert_allclose(lp16, lp32, atol=0.05, rtol=0.02)


@pytest.mark.slow  # 4000-draw statistical leg; the bf16 sampling path
# itself is pinned fast by test_bfloat16_logits_supported
def test_bfloat16_sampled_distribution_close():
    """Stochastic sampling from bf16 logits matches the f32 categorical
    distribution (chi-square-ish tolerance over many draws)."""
    V = 16
    logits = jnp.asarray(
        np.array([np.linspace(0, 3, V)], dtype=np.float32)
    )
    l16 = logits.astype(jnp.bfloat16)
    n = 4000
    counts = np.zeros(V)
    for i in range(n // 50):
        toks = sample(
            jnp.broadcast_to(l16, (50, V)), jax.random.PRNGKey(i),
            temperature=np.ones(50, np.float32),
            top_p=np.ones(50, np.float32),
        )
        for t in np.asarray(toks):
            counts[t] += 1
    p = np.exp(np.asarray(logits[0]))
    p /= p.sum()
    # every high-probability bucket within 30% relative
    big = p > 0.05
    np.testing.assert_allclose(
        counts[big] / n, p[big], rtol=0.3
    )


def test_head_returns_float32_logits_for_bfloat16_activations():
    """head_apply hands sampling float32 logits whatever the activation
    dtype: greedy parity with the reference (tests/test_golden.py) rests
    on an f32 argmax."""
    from sutro_tpu.models import transformer
    from sutro_tpu.models.configs import MODEL_CONFIGS

    cfg = MODEL_CONFIGS["tiny-dense"]
    params = transformer.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16
    )
    h = jnp.zeros((1, 4, cfg.hidden_size), jnp.bfloat16)
    vlen = jnp.full((1,), 4, jnp.int32)

    logits, h_out = transformer.head_apply(cfg, params, h, vlen)
    assert logits.dtype == jnp.float32
    assert h_out.dtype == jnp.bfloat16


def test_apply_penalties_preserves_dtype():
    """bf16 logits stay bf16 through the penalties path (the bandwidth
    saving must not silently evaporate for penalized rows)."""
    B, V = 2, 32
    logits = jnp.zeros((B, V), jnp.bfloat16)
    seen = jnp.zeros((B, V), bool)
    ids_p = jnp.full((B, 4), -1, jnp.int32)
    cnt_p = jnp.zeros((B, 4), jnp.float32)
    out = apply_penalties(
        logits, seen, ids_p, cnt_p,
        presence=jnp.full((B,), 0.5, jnp.float32),
        frequency=jnp.full((B,), 0.5, jnp.float32),
        repetition=jnp.full((B,), 1.2, jnp.float32),
    )
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------
# A batch whose rows are all greedy is sampled by its argmax alone
# ---------------------------------------------------------------------


def _sample_before(
    logits, key, *, temperature, top_p, top_k=0, allowed=None,
    row_seeds=None,
):
    """``sample`` as it stood before it chose, by ``lax.cond`` on
    ``all(temperature <= 0)``, between the argmax alone and the whole
    stochastic path: the straight-line body, kept here as the reference
    every batch must still match id for id."""
    from sutro_tpu.ops.sampling import NEG_INF, NUCLEUS_CAP

    B, V = logits.shape
    if allowed is not None:
        logits = jnp.where(allowed, logits, jnp.asarray(NEG_INF, logits.dtype))
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None].astype(
        logits.dtype
    )
    K = min(NUCLEUS_CAP, V)

    def _exact():
        return jax.lax.top_k(scaled, K)

    def _approx():
        return jax.lax.approx_max_k(
            scaled, K, recall_target=0.95, aggregate_to_topk=True
        )

    if allowed is not None:
        top_vals, top_idx = _exact()
    else:
        top_vals, top_idx = jax.lax.cond(
            jnp.any((top_k > 0) & (top_k <= 32)), _exact, _approx
        )
    greedy_tok = jnp.argmax(scaled, axis=-1).astype(jnp.int32)
    lse = jax.scipy.special.logsumexp(
        scaled.astype(jnp.float32), axis=-1, keepdims=True
    )
    top_vals = top_vals.astype(jnp.float32)
    probs = jnp.exp(top_vals - lse)
    ranks = jnp.arange(K, dtype=jnp.int32)[None, :]
    k_active = top_k > 0
    k_eff = jnp.where(k_active, jnp.minimum(top_k, K), K)[:, None]
    keep_k = ranks < k_eff
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < top_p[:, None]
    vals = jnp.where(keep_k & keep_p, top_vals, NEG_INF)
    filtered = k_active | (top_p < 1.0)
    if row_seeds is not None:
        keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(row_seeds)
        g_head = jax.vmap(
            lambda k, lg: jax.random.gumbel(k, lg.shape, jnp.float32)
        )(keys, vals)
        choice = jnp.argmax(vals + g_head, axis=-1)
        g_full = jax.vmap(
            lambda k, lg: jax.random.gumbel(
                jax.random.fold_in(k, 1), lg.shape, jnp.float32
            )
        )(keys, scaled)
        full_tok = jnp.argmax(scaled + g_full, axis=-1)
    else:
        choice = jax.random.categorical(key, vals, axis=-1)
        full_tok = jax.lax.cond(
            jnp.all(filtered | (temperature <= 0.0)),
            lambda: jnp.zeros((B,), jnp.int32),
            lambda: jax.random.categorical(
                jax.random.fold_in(key, 1),
                scaled.astype(jnp.float32),
                axis=-1,
            ).astype(jnp.int32),
        )
    head_tok = jnp.take_along_axis(top_idx, choice[:, None], axis=1)[:, 0]
    sampled = jnp.where(filtered, head_tok, full_tok)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled).astype(jnp.int32)


_B, _V = 8, 640
_TEMPERATURES = {
    "all_greedy": np.zeros(_B, np.float32),
    "mixed": np.asarray([0.0, 0.7, 0.0, 1.3, 0.0, 0.0, 0.9, 0.0], np.float32),
    "all_drawing": np.linspace(0.5, 1.5, _B).astype(np.float32),
}


def _batch(dtype, masked, seeded, filters):
    """One batch's ``(logits, kwargs)`` without its temperatures."""
    rng = np.random.default_rng(53)
    logits = jnp.asarray(rng.normal(0, 2, (_B, _V)), jnp.float32).astype(dtype)
    kw = {
        "top_p": np.ones(_B, np.float32),
        "top_k": np.zeros(_B, np.int32),
        "allowed": None,
        "row_seeds": None,
    }
    if filters:
        # a row of every kind: nucleus alone, a small top-k (the exact
        # head's cond), a wide one, both, and rows with neither
        kw["top_p"] = np.asarray(
            [0.9, 1.0, 1.0, 0.5, 1.0, 0.95, 1.0, 1.0], np.float32
        )
        kw["top_k"] = np.asarray([0, 4, 100, 8, 0, 0, 0, 300], np.int32)
    if masked:
        allowed = rng.random((_B, _V)) < 0.02
        allowed[:, 5] = True           # no row without a token
        allowed[3] = False
        allowed[3, 17] = True          # a row of one token
        kw["allowed"] = jnp.asarray(allowed)
    if seeded:
        kw["row_seeds"] = jnp.asarray(
            rng.integers(0, 2**31 - 1, _B), jnp.int32
        )
    return logits, kw


_CASES = [
    pytest.param(
        dtype, masked, seeded, filters,
        id="-".join((
            dtype, "masked" if masked else "plain",
            "row_seeds" if seeded else "one_key",
            "top_k_top_p" if filters else "unfiltered",
        )),
    )
    for dtype in ("float32", "bfloat16")
    for masked in (False, True)
    for seeded in (False, True)
    for filters in (False, True)
]


@pytest.mark.parametrize("batch", list(_TEMPERATURES))
@pytest.mark.parametrize("dtype,masked,seeded,filters", _CASES)
def test_sample_returns_what_it_returned_before(
    dtype, masked, seeded, filters, batch
):
    """An all-greedy, a mixed and an all-drawing batch each return, id
    for id on the same key, what the straight-line body returned."""
    logits, kw = _batch(dtype, masked, seeded, filters)
    kw["temperature"] = _TEMPERATURES[batch]
    for k in (0, 7):
        key = jax.random.PRNGKey(k)
        now = jax.jit(sample)(logits, key, **kw)
        before = jax.jit(_sample_before)(logits, key, **kw)
        assert now.dtype == jnp.int32 and now.shape == (_B,)
        np.testing.assert_array_equal(np.asarray(now), np.asarray(before))


@pytest.mark.parametrize("dtype,masked,seeded,filters", _CASES)
def test_an_all_greedy_batch_is_the_argmax_of_its_masked_logits(
    dtype, masked, seeded, filters
):
    logits, kw = _batch(dtype, masked, seeded, filters)
    kw["temperature"] = _TEMPERATURES["all_greedy"]
    x = np.asarray(logits.astype(jnp.float32))
    if masked:
        x = np.where(np.asarray(kw["allowed"]), x, -np.inf)
    want = x.argmax(-1)
    # the key is not read: any two give the same ids
    for k in (0, 1):
        got = sample(logits, jax.random.PRNGKey(k), **kw)
        np.testing.assert_array_equal(np.asarray(got), want)


_HEAD_AND_DRAW = (
    "top_k", "approx_top_k", "sort", "cumsum", "cumlogsumexp",
    "random_bits", "random_wrap", "random_unwrap", "random_seed",
    "random_fold_in", "threefry2x32",
)


def _primitives(jaxpr):
    """Names of every primitive of ``jaxpr``, sub-jaxprs included."""
    from jax.extend import core as jex_core

    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    out |= _primitives(sub)
    return out


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("seeded", [False, True], ids=["one_key", "row_seeds"])
def test_the_greedy_branch_holds_no_head_and_no_draw(masked, seeded):
    """On the jaxpr: ``sample`` is ONE cond at its top level, whose
    greedy branch (the one ``all(temperature <= 0)`` selects) is an
    argmax and nothing else: no head, no probabilities, no cumulative
    sum, no draw; the other branch holds all of them. Beside the cond,
    for both sides: the mask, the divide and the logsumexp of the scaled
    logits (``sample`` says why they stay there)."""
    logits, kw = _batch("float32", masked, seeded, True)
    kw["temperature"] = _TEMPERATURES["mixed"]
    jaxpr = jax.make_jaxpr(
        lambda lg, key, kw: sample(lg, key, **kw)
    )(logits, jax.random.PRNGKey(0), kw).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    # no head and no draw beside the cond
    assert not _primitives(
        jaxpr.replace(eqns=[e for e in jaxpr.eqns if e is not conds[0]])
    ) & set(_HEAD_AND_DRAW)
    # ``lax.cond(pred, true_fn, false_fn)`` indexes its branches by the
    # predicate: branches[1] is the one taken when it holds
    drawn, greedy = (_primitives(b.jaxpr) for b in conds[0].params["branches"])
    assert greedy == {"argmax"}, greedy
    for name in ("top_k", "cumsum", "random_bits", "exp"):
        assert name in drawn, (name, sorted(drawn))
    if not masked:
        assert "approx_top_k" in drawn
