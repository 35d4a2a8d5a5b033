"""A routed layer round its grouped products (ops/moe.py, PR 44): ONE
sort of the expanded rows, group sizes and counts by compare and sum,
and the rows back to their tokens by the sort's inverse and a reduce
over top_k in float32 (``combine``), never a scatter where every row is
live. Every property is one parametrised test, each case counted."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sutro_tpu.ops import lowering, moe

H, F = 16, 24


def _layer(seed, N, E, *, held=None, two=False, layers=None, biases=False):
    """``(x, router, gate, up, down, keywords)`` in float32."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    n = lambda *s: jax.random.normal(next(ks), s, jnp.float32)  # noqa: E731
    stack = (held or E,) if layers is None else (layers, held or E)
    x, router = n(1, N, H), n(H, E)
    gate = None if two else n(*stack, H, F) * 0.2
    up = n(*stack, F, H) * 0.2 if two else n(*stack, H, F) * 0.2
    down = n(*stack, F, H) * 0.2
    kw = {}
    if biases:
        kw = dict(
            router_b=n(E), bias_gate=n(E, F) * 0.1, bias_up=n(E, F) * 0.1,
            bias_down=n(E, H) * 0.1, activation="swiglu_oss",
        )
    if two:
        kw["activation"] = "relu2"
    return x, router, gate, up, down, kw


#: name -> (what ``_layer`` builds, ``moe_mlp``'s further keywords)
CASES = {
    "softmax": (dict(N=37, E=16), dict(top_k=4)),
    "softmax_as_it_is": (
        dict(N=21, E=16), dict(top_k=4, route=dict(renorm=False)),
    ),
    "sigmoid_with_bias": (
        dict(N=29, E=16),
        dict(top_k=4, route=dict(
            score="sigmoid", scale=2.5,
            select_bias=jnp.linspace(-1.0, 1.0, 16),
        )),
    ),
    "two_matrices_relu2": (dict(N=33, E=16, two=True), dict(top_k=2)),
    "gpt_oss_biases": (dict(N=19, E=16, biases=True), dict(top_k=4)),
    "layer_stack": (dict(N=23, E=16, layers=3), dict(top_k=4, layer=1)),
    # 5 x 3 = 15 expanded rows: not a multiple of 8
    "rows_off_the_sublane": (dict(N=5, E=16), dict(top_k=3)),
    "half_share": (
        dict(N=40, E=16, held=8, two=True),
        dict(top_k=3, first_expert=8),
    ),
    "half_share_stack": (
        dict(N=24, E=16, held=8, layers=2),
        dict(top_k=4, first_expert=0, layer=1),
    ),
    # a sixteenth over 4,096 rows: capped at 512 (``_share_row_cap``).
    # An even router sends the two held experts ~256 rows: the capped
    # branch of the ``lax.cond``
    "sixteenth_share_even": (
        dict(N=1024, E=32, held=2), dict(top_k=4, first_expert=6),
    ),
    # ... and a router that sends EVERY token to both held experts,
    # 2,048 rows: every row goes through, the other branch
    "sixteenth_share_crowded": (
        dict(N=1024, E=32, held=2),
        dict(top_k=4, first_expert=6, router_b=jnp.zeros((32,)).at[6:8].set(50.0)),
    ),
}


def _dense_and_ragged(name, **more):
    build, kw = CASES[name]
    x, router, gate, up, down, built = _layer(len(name), **build)
    kw = {**built, **kw, **more}
    call = functools.partial(moe.moe_mlp, x, router, gate, up, down, **kw)
    return call, kw


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_ragged_path_equals_the_dense_one(name):
    """In float32 the two methods are the same sum in another order."""
    call, kw = _dense_and_ragged(name)
    want = call(method="dense")
    before = lowering.moe_combine_counts()
    got = jax.jit(lambda: call(method="ragged"))()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )
    after = lowering.moe_combine_counts()
    traced = {k: after[k] - before[k] for k in after}
    # a capped share traces both branches of its ``lax.cond``: the first
    # ``cap`` rows (the rest read from a zero row) and every row
    branches = 2 if name.startswith("sixteenth") else 1
    assert traced == {"unpermuted": branches, "scattered": 0}


@pytest.mark.parametrize("crowded", [False, True])
def test_a_capped_share_takes_the_branch_its_rows_ask_for(crowded):
    """The two ``sixteenth`` cases above do run different branches: the
    held experts' rows are under the cap of 512 in one and over it in
    the other."""
    name = "sixteenth_share_crowded" if crowded else "sixteenth_share_even"
    build, kw = CASES[name]
    x, router, *_ = _layer(len(name), **build)
    _, _, flat_expert, _, _ = moe._route(
        x[0], router, kw.get("router_b"), kw["top_k"]
    )
    held, first = build["held"], kw["first_expert"]
    cap = moe._share_row_cap(flat_expert.shape[0], held, build["E"])
    assert cap == 512
    owned = int(jnp.sum(moe.held_key(flat_expert, first, held) < held))
    assert (owned > cap) == crowded


@pytest.mark.parametrize("name", ["softmax", "half_share", "sixteenth_share_crowded"])
def test_the_counts_are_a_bincount_of_the_routers_choices(name):
    """``return_counts``: rows an expert of the ROUTER's, held or not."""
    call, kw = _dense_and_ragged(name, return_counts=True)
    build, _ = CASES[name]
    x, router, *_ = _layer(len(name), **build)
    _, _, flat_expert, _, _ = moe._route(
        x[0], router, kw.get("router_b"), kw["top_k"], **kw.get("route", {})
    )
    want = np.bincount(np.asarray(flat_expert), minlength=build["E"])
    for method in ("dense", "ragged"):
        _, counts = call(method=method)
        assert counts.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(counts), want)


@pytest.mark.parametrize("first,held", [(0, 4), (4, 4), (12, 4), (3, 1), (0, 16)])
def test_held_rows_keeps_its_order_and_sizes(first, held):
    """Own rows first, grouped by local expert, token order kept inside
    a group (a stable sort); the others after, ``local`` = ``held`` and
    weight 0; the tail counted onto the LAST held group."""
    rng = np.random.default_rng(first * 17 + held)
    N, K, E = 50, 3, 16
    flat_expert = rng.integers(0, E, N * K).astype(np.int32)
    probs = rng.random((N, K)).astype(np.float32) + 0.1
    local, order, sizes = map(np.asarray, jax.jit(
        lambda e: moe.held_rows(e, first, held)
    )(flat_expert))
    loc = flat_expert - first
    owned = (loc >= 0) & (loc < held)
    key = np.where(owned, loc, held)
    want_order = np.argsort(key, kind="stable")
    np.testing.assert_array_equal(order, want_order)
    np.testing.assert_array_equal(local, key[want_order])
    want_sizes = np.bincount(key, minlength=held + 1)
    want_sizes[held - 1] += want_sizes[held]
    np.testing.assert_array_equal(sizes, want_sizes[:held])
    assert sizes.sum() == N * K and sizes.dtype == np.int32
    # a sorted row's token, with no gather of a token vector
    np.testing.assert_array_equal(
        order // K, np.repeat(np.arange(N), K)[want_order]
    )
    np.testing.assert_array_equal(
        np.asarray(moe.held_weights(
            flat_expert.reshape(N, K), probs, first, held
        )),
        np.where(owned.reshape(N, K), probs, 0.0),
    )


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize(
    "name", ["softmax", "sigmoid_with_bias", "layer_stack", "half_share"]
)
def test_the_uncapped_path_holds_no_scatter_and_no_scalar_gather(name):
    """Round the products: no ``scatter-add`` (the chip's lowering of a
    row scatter-add sorts its indices again, gathers the updates into
    that order and then scatters), and no gather from a vector of the
    ``M`` expanded rows (``x[order]``: what must lie in sorted order
    rides the sort). ``bincount`` was a scatter-add too."""
    call, kw = _dense_and_ragged(name, return_counts=True)
    build, _ = CASES[name]
    M = build["N"] * kw["top_k"]
    jaxpr = jax.make_jaxpr(lambda: call(method="ragged"))()
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert not [n for n in names if n.startswith("scatter")], names
    assert "while" not in names
    assert names.count("sort") == 2  # the layer's, and its inverse
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "gather":
            operand = eqn.invars[0].aval
            assert operand.shape != (M,), eqn
    # what IS gathered: rows of [tokens, H] in front of the products and
    # of [M, H] behind them (and a bias row an expert where there is one)
    gathered = [
        e.invars[0].aval.shape for e in _eqns(jaxpr.jaxpr)
        if e.primitive.name == "gather"
    ]
    assert (build["N"], H) in gathered and (M, H) in gathered


def test_a_capped_share_reads_the_rows_behind_its_cap_from_a_zero_row():
    """``combine`` over the first ``R`` sorted rows alone: the rows
    behind them come from ONE zero row, whatever ``y`` holds (an inf in
    a live row stays in its own token)."""
    N, K, R = 16, 4, 24
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    order = jax.random.permutation(ks[0], N * K).astype(jnp.int32)
    y = jax.random.normal(ks[1], (N * K, H), jnp.float32)
    y = y.at[R - 1].set(jnp.inf)
    inverse = jnp.argsort(order)
    live = (inverse < R).reshape(N, K)
    weights = jnp.where(live, jax.random.uniform(ks[2], (N, K)) + 0.1, 0.0)
    got = moe.combine(y[:R], order, weights)
    want = moe.combine(jnp.where(jnp.arange(N * K)[:, None] < R, y, 0.0), order, weights)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(jnp.sum(jnp.isinf(got).any(axis=1))) == 1


def test_the_combine_sums_in_float32_and_rounds_once():
    """bf16 rows: the combine is the float32 sum of a token's weighted
    rows, rounded once (a bf16 scatter-add rounds after every row, in
    the order the chip happens to take them)."""
    N, K = 64, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    y = jax.random.normal(ks[0], (N * K, H), jnp.float32).astype(jnp.bfloat16)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (N, K)), axis=-1)
    order = jax.random.permutation(ks[2], N * K).astype(jnp.int32)
    got = moe.combine(y, order, weights)
    assert got.dtype == jnp.bfloat16
    inverse = np.argsort(np.asarray(order))
    back = np.asarray(y, np.float32)[inverse].reshape(N, K, H)
    want = (back * np.asarray(weights)[:, :, None]).sum(axis=1)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32),
    )


@pytest.mark.parametrize("with_bias", [False, True])
def test_the_expert_parallel_path_combines_the_same_way(eight_devices, with_bias):
    """``moe_mlp_ep`` calls ``held_rows`` and ``combine`` of ops/moe.py:
    a mesh of two expert shards equals the dense method, and its body
    holds no scatter either."""
    from sutro_tpu.ops.moe_ep import moe_mlp_ep
    from sutro_tpu.parallel.mesh import make_mesh

    x, router, gate, up, down, kw = _layer(3, N=12, E=4, biases=with_bias)
    x = x.reshape(2, 6, H)
    kw["top_k"] = 2
    want = moe.moe_mlp(x, router, gate, up, down, method="dense", **kw)
    mesh = make_mesh(1, 2, 1, eight_devices[:2])
    fn = lambda *a: moe_mlp_ep(*a, mesh=mesh, **kw)  # noqa: E731
    before = lowering.moe_combine_counts()
    got = jax.jit(fn)(x, router, gate, up, down)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )
    after = lowering.moe_combine_counts()
    assert after["unpermuted"] == before["unpermuted"] + 1
    assert after["scattered"] == before["scattered"]
    jaxpr = jax.make_jaxpr(fn)(x, router, gate, up, down)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert not [n for n in names if n.startswith("scatter")], names


def test_the_device_report_shows_the_combine():
    from sutro_tpu.engine.runner import device_report

    assert device_report()["moe_combine"] == lowering.moe_combine_counts()
    assert set(lowering.moe_combine_counts()) == set(lowering.MOE_COMBINE)
