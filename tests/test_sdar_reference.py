"""``tiny-sdar`` (Qwen3-MoE's layer under the block mask) against the
plain reference ``perfbench/reference/sdar_moe.py``, logits not tokens:
the system's prefill, then blocks through the paged cache by its own
``forced_logits`` (a denoising and a commit program a block), against
the reference's ONE full forward under the block mask; the same with 1-4
mask tokens in the block; the two programs on the same block;
``block_length`` 1 is the causal model to the bit; and the four ways the
mechanism could be built wrong, each of which fails the tolerance the
true form passes.

Tolerances, each with its reason. float32 / float32: 2e-5 of the largest
logit (the measured 6e-7 with room for another backend's summation
order; no routing flips at this size, checked by the near-tie count).
bf16 weights and activations against the float32 reference: under 0.06
(``tolerance.json``'s bfloat16: measured 0.01-0.03) and OVER 2e-3, so
that bf16 under a float32 configuration fails float32's limit. The
controls move the logits by 0.04 or more of the largest."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import sdar_moe
from tests.sdar_common import (
    BK, KEYS, MASK, MCFG, engine, err, runner, sequence, want,
)

F32_TOL, BF16_TOL = 2e-5, 0.06
N_PRE, N_DEC = 40, 8
POSITIONS = list(range(N_PRE - 1, N_PRE + N_DEC))


def test_prefill_then_blocks_match_the_full_forward_float32():
    r = runner()
    for seed in (1, 2):
        seq = sequence(seed, N_PRE + N_DEC)
        got = r.forced_logits(seq, N_PRE, N_DEC)
        assert got.shape == (1 + N_DEC, MCFG.vocab_size)
        assert err(got, want(r.params, seq, POSITIONS)) < F32_TOL
    # no position of these sat on a routing tie: the tolerance is the
    # summation order's, not a flipped expert's
    _, ties = sdar_moe.logits_and_near_ties(KEYS, r.params, seq, POSITIONS)
    assert int(np.asarray(ties).max()) <= 1


@pytest.mark.parametrize("masks", [1, 2, 3, 4])
def test_a_block_holding_masks_is_the_references_full_forward(masks):
    """The denoising forward as the timed path feeds it: ``forced_logits``
    over ids whose last block holds mask tokens."""
    r = runner()
    seq = sequence(3, N_PRE + N_DEC)
    seq[N_PRE + BK + np.random.default_rng(masks).permutation(BK)[:masks]] = MASK
    got = r.forced_logits(seq, N_PRE, N_DEC)
    assert err(got, want(r.params, seq, POSITIONS)) < F32_TOL


def test_chunked_prefill_under_the_block_mask():
    """A prompt over ``prefill_chunk`` (16) is prefilled a chunk at a
    time over the paged past, each chunk under the block mask."""
    r = runner()
    seq = sequence(4, 56)
    got = r.forced_logits(seq, 48, 8)
    assert err(got, want(r.params, seq, range(47, 56))) < F32_TOL


def test_the_denoising_and_the_commit_program_agree_on_a_block():
    """One forward, two programs: the K/V the commit program writes for
    a block is what a later block's denoising forward sees, so logits
    after a committed block equal the reference's; and the denoising
    program leaves the cache as it found it."""
    r = runner()
    seq = sequence(5, N_PRE + N_DEC)
    table = np.zeros((r.ecfg.max_pages_per_seq,), np.int32)
    table[:6] = np.arange(1, 7)
    r.prefill(seq[:N_PRE], table)
    before = np.asarray(r.cache.k_pages)
    x = jnp.asarray(seq[None, N_PRE : N_PRE + BK])
    start = jnp.asarray([N_PRE], jnp.int32)
    tdev = jnp.asarray(table[None])
    a = np.asarray(r._decode_block_denoise_jit(r.params, r.cache, x, start, tdev))
    np.testing.assert_array_equal(before, np.asarray(r.cache.k_pages))
    b = np.asarray(r._decode_block_denoise_jit(r.params, r.cache, x, start, tdev))
    np.testing.assert_array_equal(a, b)
    r.cache = r._decode_block_commit_jit(r.params, r.cache, x, start, tdev)
    assert not np.array_equal(before, np.asarray(r.cache.k_pages))
    nxt = r._decode_block_denoise_jit(
        r.params, r.cache, jnp.asarray(seq[None, N_PRE + BK : N_PRE + 2 * BK]),
        start + BK, tdev,
    )
    assert err(np.asarray(nxt[0]),
               want(r.params, seq, range(N_PRE + BK, N_PRE + 2 * BK))) < F32_TOL


def test_bf16_is_within_its_tolerance_and_outside_float32s():
    r = runner(param_dtype="bfloat16", activation_dtype="bfloat16")
    errs = []
    for seed in (6, 7):
        seq = sequence(seed, N_PRE + N_DEC)
        got = r.forced_logits(seq, N_PRE, N_DEC)
        e = correctness.position_errors(got, want(r.params, seq, POSITIONS))
        errs.extend(e.tolist())
    # a routed model is held by a quantile (a flipped expert moves a
    # position by far more than rounding): the lower quartile
    assert 2e-3 < float(np.quantile(errs, 0.25)) < BF16_TOL


def test_block_length_one_is_the_causal_model_to_the_bit():
    """The same weights under ``block_length`` 1: the causal mask, the
    program every other model runs (forward over a chunk), equal to the
    reference's causal control; and the block mask of 4 differs."""
    r = runner()
    seq = sequence(8, 24)
    causal_cfg = dataclasses.replace(MCFG, block_length=1, mask_token_id=-1)
    from sutro_tpu.models import transformer

    ids = jnp.asarray(seq[None])
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    n = jnp.asarray([24], jnp.int32)
    one = transformer.forward(causal_cfg, r.params, ids, pos, n)[0][0]
    blk = transformer.forward(MCFG, r.params, ids, pos, n)[0][0]
    ref_causal = np.asarray(sdar_moe.logits_at(
        KEYS, r.params, seq, list(range(24)), (0, 24)))
    assert err(np.asarray(one), ref_causal) < F32_TOL
    assert err(np.asarray(blk), want(r.params, seq, range(24))) < F32_TOL
    assert err(np.asarray(blk), ref_causal) > 0.01
    # to the bit: the jaxpr of a causal config names no block mask
    text = str(jax.make_jaxpr(
        lambda p: transformer.forward(causal_cfg, p, ids, pos, n)[0]
    )(r.params))
    assert "floor_divide" not in text and "bd_" not in text


@pytest.mark.parametrize("control", sdar_moe.CONTROLS)
def test_each_mechanism_control_fails_the_tolerance(control):
    """The reference computing another model, scored where it moves the
    logits (``perfbench/tools/bd_numbers.py``): the true form passes
    ``F32_TOL`` there and the control misses it by orders of
    magnitude."""
    r = runner()
    seq = sequence(9, N_PRE + N_DEC)
    got = r.forced_logits(seq, N_PRE, N_DEC)
    at = {
        "causal_block": list(range(N_PRE, N_PRE + N_DEC)),
        "stale_commit": list(range(N_PRE + N_DEC - BK, N_PRE + N_DEC)),
    }.get(control, POSITIONS)
    rows = [p - (N_PRE - 1) for p in at]
    assert err(got[rows], want(r.params, seq, at)) < F32_TOL
    errs = correctness.position_errors(
        got[rows], want(r.params, seq, at, control, N_PRE))
    assert float(np.quantile(errs, 0.25)) > 100 * F32_TOL


def test_forced_logits_names_the_lengths_it_refuses():
    r = runner()
    with pytest.raises(ValueError, match="n_prefill 41 and n_decode 8"):
        r.forced_logits(sequence(1, 49), 41, 8)
    with pytest.raises(ValueError, match="whole blocks of 4"):
        r.forced_logits(sequence(1, 46), 40, 6)


def test_only_a_block_models_runner_has_the_forced_forward():
    from sutro_tpu.engine.runner import ModelRunner
    from sutro_tpu.models.configs import MODEL_CONFIGS

    assert callable(runner().forced_logits)
    dense = ModelRunner(MODEL_CONFIGS["tiny-dense"], engine())
    assert not hasattr(dense, "forced_logits")
    info = runner().device_info()
    assert (info["block_length"], info["mask_token_id"]) == (BK, MASK)
    assert dense.device_info()["block_length"] == 1
