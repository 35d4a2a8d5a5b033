"""Generation by diffusion over blocks at ``tiny-sdar``: the device's
``transfer`` against the reference's on constructed confidences (the
three rules, the threshold branch taken and not taken, ties, the even
share for every number of steps, the mask token never drawn), and the
system's tokens through the scheduler against the reference's plain
loops (``sdar_moe.generate``, every forward the whole sequence again) at
float32 and temperature 0: prompts of every length mod the block, a stop
token inside a block, a cap inside a block, ``gen_tokens`` never over
the cap.

A greedy token is compared wherever the reference's top-two logits are
over ``MARGIN`` apart: 1e-3, a thousand times the 6e-7 the two forwards
differ by (tests/test_sdar_reference.py), so that no comparison rests on
a rounding; the tokens that FOLLOW a closer call in the same row are
left out too, for they were conditioned on it."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import sdar_moe
from sutro_tpu.ops import sampling
from tests.sdar_common import BK, KEYS, MASK, MCFG, generate, runner, sequence

MARGIN = 1e-3
RULE_ID = {"low_confidence_static": sampling.STATIC,
           "low_confidence_dynamic": sampling.DYNAMIC,
           "sequential": sampling.SEQUENTIAL}


def _device_transfer(x, x0, c, n, rule, tau):
    out, taken = sampling.transfer(
        jnp.asarray([x], jnp.int32), jnp.asarray([x0], jnp.int32),
        jnp.asarray([c], jnp.float32), jnp.asarray([n], jnp.int32),
        jnp.asarray([RULE_ID[rule]], jnp.int32),
        jnp.asarray([tau], jnp.float32), MASK,
    )
    return np.asarray(out[0]).tolist(), np.asarray(taken[0]).tolist()


CONFS = [
    [0.9, 0.1, 0.5, 0.3],          # distinct
    [0.5, 0.5, 0.5, 0.5],          # all tied: leftmost first
    [0.2, 0.95, 0.95, 0.1],        # a tie at the top
    [0.91, 0.92, 0.2, 0.93],       # three over a threshold of 0.9
    [0.0, 0.0, 1.0, 0.0],
]
OPEN = [
    [MASK] * 4, [7, MASK, MASK, MASK], [MASK, 7, MASK, 8], [7, 8, 9, MASK],
]


@pytest.mark.parametrize("rule", sdar_moe.RULES)
def test_transfer_is_the_references_on_constructed_confidences(rule):
    x0 = [11, 12, 13, 14]
    seen_dynamic = set()
    for c, x, n, tau in itertools.product(
        CONFS, OPEN, range(1, BK + 1), (0.9, 0.45, 0.99)
    ):
        want = sdar_moe.transfer(x, x0, c, n, rule, tau, MASK)
        got, taken = _device_transfer(x, x0, c, n, rule, tau)
        assert got == want, (rule, c, x, n, tau)
        # only open positions were written, and only with the draw
        for i in range(BK):
            assert taken[i] == (x[i] == MASK and got[i] != MASK)
            if x[i] != MASK:
                assert got[i] == x[i]
        if rule == "low_confidence_dynamic":
            over = sum(1 for i in range(BK) if x[i] == MASK and c[i] > tau)
            seen_dynamic.add(over >= min(n, x.count(MASK)))
    if rule == "low_confidence_dynamic":
        assert seen_dynamic == {True, False}    # both branches were held


def test_the_even_share_of_every_number_of_steps_fills_a_block():
    for steps in range(1, BK + 1):
        shares = sdar_moe.shares(BK, steps)
        assert sum(shares) == BK and max(shares) - min(shares) <= 1
        assert shares == sorted(shares, reverse=True)
        x = [MASK] * BK
        for t, n in enumerate(shares):
            assert MASK in x
            x, _ = _device_transfer(
                x, [21, 22, 23, 24], [0.4, 0.3, 0.2, 0.1], n,
                "low_confidence_static", 0.9)
        assert MASK not in x


def test_the_mask_token_is_never_drawn_and_confidence_is_the_draws_own():
    V = MCFG.vocab_size
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((8, V)).astype(np.float32)
    logits[:, MASK] = 50.0                       # by far the largest
    temp = jnp.asarray([0.0, 0.0, 0.7, 0.7, 1.0, 1.0, 0.7, 0.7])
    top_p = jnp.asarray([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 0.5, 0.5])
    top_k = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 3], jnp.int32)
    tok, conf, logp = sampling.sample_with_confidence(
        jnp.asarray(logits), jax.random.PRNGKey(1), temperature=temp,
        top_p=top_p, top_k=top_k, exclude=MASK,
    )
    tok, conf, logp = map(np.asarray, (tok, conf, logp))
    assert (tok != MASK).all()
    masked = logits.copy()
    masked[:, MASK] = -np.inf
    z = masked.astype(np.float64)
    # greedy rows: the argmax, its probability under the plain softmax
    for i in (0, 1):
        p = np.exp(z[i] - z[i].max()); p /= p.sum()
        assert tok[i] == z[i].argmax()
        assert conf[i] == pytest.approx(p[tok[i]], rel=1e-4)
        assert logp[i] == pytest.approx(np.log(p[tok[i]]), rel=1e-4)
    # drawing rows without a filter: the probability at the row's own
    # temperature
    for i in (2, 3, 4, 5):
        t = float(temp[i])
        p = np.exp(z[i] / t - (z[i] / t).max()); p /= p.sum()
        assert conf[i] == pytest.approx(p[tok[i]], rel=1e-4)
    # filtered rows: renormalised over what the filters keep
    for i in (6, 7):
        t = float(temp[i])
        p = np.exp(z[i] / t - (z[i] / t).max()); p /= p.sum()
        order = np.argsort(-p)
        keep = np.zeros(V, bool)
        cum = 0.0
        for rank, j in enumerate(order):
            if cum >= float(top_p[i]) or (top_k[i] and rank >= int(top_k[i])):
                break
            keep[j] = True
            cum += p[j]
        assert keep[tok[i]]
        assert conf[i] == pytest.approx(p[tok[i]] / p[keep].sum(), rel=1e-3)
    assert ((0 < conf) & (conf <= 1)).all()


def _agree(got, want, gaps):
    """Tokens equal up to the first position whose top-two gap is under
    the margin (what follows was conditioned on a coin's toss)."""
    for g, w, gap in zip(got, want, gaps):
        if gap < MARGIN:
            return
        assert g == w, (got, want, gaps)
    assert len(got) == len(want)


LENGTHS = (5, 6, 7, 8, 33)      # every length mod 4; 33 > prefill_chunk 16
SETTINGS = [
    (2, "low_confidence_static", 0.9),
    (4, "low_confidence_dynamic", 0.004),   # the threshold bites at V = 512
    (3, "sequential", 0.9),
    (1, "low_confidence_static", 0.9),
]


@pytest.mark.parametrize("steps,rule,tau", SETTINGS)
def test_the_systems_tokens_are_the_references(steps, rule, tau):
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    r = runner()
    prompts = [sequence(40 + n, n) for n in LENGTHS]
    caps = [10, 11, 12, 13, 14]          # every cap mod 4, none a block's edge only
    res = generate(
        ContinuousBatcher(r, stop_ids=[]), prompts, caps,
        denoising_steps=steps, remasking=rule, confidence_threshold=tau,
    )
    for i, (p, cap) in enumerate(zip(prompts, caps)):
        want, gaps = sdar_moe.generate(KEYS, r.params, p, cap, steps, rule, tau)
        assert len(res[i].token_ids) == cap and res[i].finish_reason == "length"
        assert MASK not in res[i].token_ids
        _agree(res[i].token_ids, want, gaps)


def test_a_stop_token_inside_a_block_ends_its_row_there():
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    r = runner()
    prompts = [sequence(60 + n, n) for n in (9, 10, 12, 7, 21, 6)]
    free = generate(ContinuousBatcher(r, stop_ids=[]), prompts, [16] * 6,
                    denoising_steps=2, remasking="low_confidence_static")
    held = 0
    for i, p in enumerate(prompts):
        out = free[i].token_ids
        # a token the row first emits where its block goes on behind it
        # becomes the stop token: what follows it in its block is lost
        at = next((j for j in range(1, 15)
                   if (len(p) + j) % BK != BK - 1 and out[j] not in out[:j]),
                  None)
        if at is None:
            continue
        held += 1
        res = generate(ContinuousBatcher(r, stop_ids=[out[at]]), [p], [16],
                       denoising_steps=2, remasking="low_confidence_static")[0]
        assert res.finish_reason == "stop"
        assert res.token_ids == out[:at]       # the stop token is dropped
        want, _ = sdar_moe.generate(
            KEYS, r.params, p, 16, 2, "low_confidence_static", 0.9,
            stop_ids=[out[at]])
        assert want[-1] == out[at] and len(want) == at + 1
    assert held >= 2


def test_gen_tokens_never_pass_the_cap_whatever_it_is():
    from sutro_tpu.engine.scheduler import ContinuousBatcher

    r = runner()
    prompts = [sequence(80 + c, 5 + c % 7) for c in range(1, 10)]
    caps = list(range(1, 10))
    res = generate(ContinuousBatcher(r, stop_ids=[]), prompts, caps,
                   temperature=0.8, denoising_steps=2,
                   remasking="low_confidence_static")
    for i, cap in enumerate(caps):
        assert len(res[i].token_ids) == cap, (i, cap)
        assert res[i].input_tokens == len(prompts[i])
