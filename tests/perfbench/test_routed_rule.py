"""The finding and the rule (``reference/README.md``), on the CPU: a
correct bf16 system of a routed model FAILS the position-by-position
rule against the float32 reference, because rounding flips the 8th
against the 9th expert at some positions; the routed rule passes it on
every seed and fails every wrong system tried. The program's own
``forward`` in bf16 stands for the system (128 experts of 96, top-8,
hidden 256, 2 heads x 128, 8 layers, vocabulary 2048), so no time and no
device number comes from here; a chip configuration's ``numbers`` values
come from chip readings.

The test prints what it read, so both margins are on record:
``python -m pytest tests/perfbench/test_routed_rule.py -s``."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import correctness
from perfbench.reference import qwen3_moe
from tests.perfbench.routed_systems import (
    NUMBERS, ForwardSystem, preset, published_keys, wrong_systems,
)
from sutro_tpu.models import transformer

SEEDS = [0, 1, 2, 3, 2**31 + 7]
WRONG_SEEDS = SEEDS[:2]
WRONG = ["top-7 in the system", "weights through float8_e4m3",
         "rope_theta 1e4 for 1e6", "norm_topk_prob false in the reference",
         "scored one position early"]
TOL = json.loads(
    (Path(correctness.__file__).parent / "reference/tolerance.json").read_text()
)["bfloat16"]
MCFG = preset()


@pytest.fixture(scope="module")
def weights():
    made = {}

    def of(seed):
        if seed not in made:
            made.clear()            # one seed's weights alive at a time
            made[seed] = transformer.init_params(
                MCFG, jax.random.PRNGKey(seed % (2**31 - 1)), "bfloat16"
            )
        return made[seed]

    return of


@pytest.fixture(scope="module")
def memo():
    """A sequence's logits, the system's and the reference's, computed
    once in this module: here the weights and the sequences both follow
    from the seed, so the sequence's bytes (with the system's
    configuration, or the reference's ``norm_topk_prob``) name them."""
    kept = {}
    plain = qwen3_moe.logits_and_near_ties

    def once(cfg, params, ids, positions):
        key = ("reference", cfg["norm_topk_prob"], np.asarray(ids).tobytes(),
               tuple(positions))
        if key not in kept:
            kept[key] = plain(cfg, params, ids, positions)
        return kept[key]

    patch = pytest.MonkeyPatch()
    patch.setattr(qwen3_moe, "logits_and_near_ties", once)
    yield kept
    patch.undo()


def say(seed, name, problems, facts):
    print(
        f"seed {seed} {name}: quantile {facts['rel_err_quantile']:.4f} "
        f"largest {facts['rel_err_max']:.4f} over tolerance "
        f"{facts['share_over_tolerance']:.2f} near ties a position "
        f"{facts['near_ties_mean']:.2f} -> {len(problems)} problem(s)"
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_a_correct_bf16_system_passes_the_routed_rule_and_not_the_old_one(
    weights, memo, seed
):
    params = weights(seed)
    keys = published_keys(MCFG)
    sut = ForwardSystem(MCFG, params, memo=memo, tag=(seed, "as stated"))
    problems, facts = correctness.numbers(sut, keys, seed)
    say(seed, "correct", problems, facts)
    assert problems == []
    assert facts["rule"] == "routed" and facts["positions"] == 36
    # room on the passing side: the quantile is under half the tolerance
    assert facts["rel_err_quantile"] < 0.5 * TOL
    assert facts["rel_err_max"] < NUMBERS["cap"]
    # the finding: some of its positions are over the dense tolerance ...
    assert facts["share_over_tolerance"] > 0.05 and facts["rel_err_max"] > TOL
    assert 0 < facts["near_ties_mean"] < MCFG.num_layers
    # ... so the position-by-position rule calls a correct system wrong
    ids = np.random.default_rng([seed, 0x1095]).integers(
        0, 256, (NUMBERS["sequences"], 200)).astype(np.int32)
    positions = list(range(191, 200))
    got = sut.logits_through_cache(ids, 192, 8)
    want = np.stack([
        np.asarray(qwen3_moe.logits_and_near_ties(keys, params, seq, positions)[0])
        for seq in ids
    ])
    errs = correctness.position_errors(got, want)
    refused = [
        bool(correctness.elementwise_rule(e, TOL, "bfloat16", positions))
        for e in errs
    ]
    print(f"seed {seed} position by position: refused {sum(refused)} of "
          f"{len(refused)} sequences of a correct system")
    assert any(refused)


@pytest.mark.parametrize("name", WRONG)
@pytest.mark.parametrize("seed", WRONG_SEEDS)
def test_a_wrong_system_fails_the_routed_rule(weights, memo, seed, name):
    sut, keys = wrong_systems(MCFG, weights(seed), memo, seed)[name]()
    problems, facts = correctness.numbers(sut, keys, seed)
    say(seed, name, problems, facts)
    assert problems, name
    # room on the failing side: what catches it is the quantile, and by far
    assert facts["rel_err_quantile"] > 1.5 * TOL


def test_what_the_routed_rule_cannot_see(weights, memo):
    """One expert of one layer zeroed in the system passes: a token in
    sixteen chooses it at that layer, and what that moves is under what
    a flip moves. A wrong expert is the kernel's unit tests' to catch."""
    seed = SEEDS[0]
    params = weights(seed)
    layers = dict(params["layers"])
    layers["we_down"] = layers["we_down"].at[3, 17].set(0)
    sut = ForwardSystem(MCFG, dict(params, layers=layers), stated=params)
    problems, facts = correctness.numbers(sut, published_keys(MCFG), seed)
    say(seed, "one expert of one layer zeroed", problems, facts)
    assert problems == []


def test_the_dense_rule_is_chosen_by_the_reference_family():
    """A configuration whose reference does not route is held position
    by position over one sequence, with the facts it always had."""
    from sutro_tpu.models.configs import MODEL_CONFIGS

    mcfg = MODEL_CONFIGS["tiny-dense"]
    params = transformer.init_params(mcfg, jax.random.PRNGKey(1), "float32")
    problems, facts = correctness.numbers(
        ForwardSystem(mcfg, params, dtype="float32"), published_keys(mcfg), 4
    )
    assert problems == []
    assert set(facts) == {"rel_err_prefill", "rel_err_decode_max", "tolerance",
                          "dtype", "kernel_paths"}


@pytest.mark.parametrize("ask", [
    {"quantile": 0.6}, {"quantile": 0.04}, {"quantile": 1}, {"cap": 0.6},
    {"cap": 0.0}, {"sequences": 3}, {"sequences": 4.0}, {"why": " "},
    {"tolerance": 0.5}, None,
    # cap_quantile: never under 0.99, a float, and only with 32 sequences
    {"cap_quantile": 0.98, "sequences": 32}, {"cap_quantile": 1, "sequences": 32},
    {"cap_quantile": 1.01, "sequences": 32}, {"cap_quantile": 0.99},
    {"cap_quantile": 0.995, "sequences": 31},
], ids=str)
def test_a_file_cannot_ask_for_more_than_the_check_allows(ask):
    cfg = published_keys(MCFG)
    if ask is None:
        del cfg["numbers"]
    else:
        cfg["numbers"].update(ask)
    with pytest.raises(ValueError):
        correctness.routed_spec(cfg)
    assert correctness.routed_spec(published_keys(MCFG))["quantile"] == 0.25
    # what the check does allow: the key left out, the maximum stated, a
    # high quantile over enough sequences, the range's low end
    fine = published_keys(MCFG)
    fine["numbers"].update(cap_quantile=1.0)
    assert correctness.routed_spec(fine)["cap_quantile"] == 1.0
    fine["numbers"].update(cap_quantile=0.99, sequences=32, quantile=0.05)
    assert correctness.routed_spec(fine)["sequences"] == 32


def test_the_routed_rule_on_hand_made_errors():
    spec, where = dict(NUMBERS), [f"p{i}" for i in range(36)]
    low = np.full(36, 0.02)
    assert correctness.routed_rule(low, TOL, "bfloat16", spec, where)[0] == []
    flipped = low.copy()
    flipped[:20] = 0.15                      # over half the positions flipped
    assert correctness.routed_rule(flipped, TOL, "bfloat16", spec, where)[0] == []
    one_far = low.copy()
    one_far[5] = 0.31
    problems, facts = correctness.routed_rule(one_far, TOL, "bfloat16", spec, where)
    assert len(problems) == 1 and "p5" in problems[0] and facts["worst"] == "p5"
    everywhere = np.full(36, 0.07)
    problems, facts = correctness.routed_rule(everywhere, TOL, "bfloat16", spec, where)
    assert len(problems) == 1 and "quantile" in problems[0]
    assert facts["share_over_tolerance"] == 1.0
    broken = low.copy()
    broken[3] = np.nan
    problems, _ = correctness.routed_rule(broken, TOL, "bfloat16", spec, where)
    assert any("not finite" in p for p in problems)
