"""The routed rule (``reference/README.md``, ``reference/lfm2_moe.md``) for the LFM2 family, on the
CPU: a correct bf16 system passes it on five seeds, and every wrong
system of the family's table fails it. The program's own ``forward`` in
bf16 stands for the system (``mixed_systems.preset``: 8 layers of two
kinds, 32 experts top-4, hidden 256), so no time and no device number
comes from here; a chip configuration's ``numbers`` values come from chip
readings. The selection bias is made ten times louder than random init
draws it (``louder_bias``): at 0.02 it moves one selection in twenty,
which a rule that forgives flipped selections cannot tell from rounding.

``python -m pytest tests/perfbench/test_lfm2_rule.py -s`` prints what it
read, so both margins are on record."""

import json
from pathlib import Path

import jax
import pytest

from perfbench import correctness
from perfbench.reference import lfm2_moe
from sutro_tpu.models import transformer
from tests.perfbench.mixed_systems import (
    NUMBERS, louder_bias, preset, published_keys, wrong_systems,
)
from tests.perfbench.routed_systems import ForwardSystem

SEEDS = [0, 1, 2, 3, 2**31 + 7]
WRONG = ["top-3 in the system", "bias left out of selection",
         "bias added into the weights", "B and C swapped",
         "conv state zeroed at every chunk boundary",
         "weights through float8_e4m3", "renormalisation dropped"]
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
            made[seed] = louder_bias(transformer.init_params(
                MCFG, jax.random.PRNGKey(seed % (2**31 - 1)), "bfloat16"
            ))
        return made[seed]

    return of


def say(seed, name, problems, facts):
    print(
        f"seed {seed} {name}: quantile {facts['rel_err_quantile']:.4f} "
        f"largest {facts['rel_err_max']:.4f} over tolerance "
        f"{facts['share_over_tolerance']:.2f} near ties a position "
        f"{facts['near_ties_mean']:.2f} -> {len(problems)} problem(s)"
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_a_correct_bf16_system_passes_the_routed_rule(weights, seed):
    sut = ForwardSystem(MCFG, weights(seed))
    problems, facts = correctness.numbers(sut, published_keys(MCFG), seed)
    say(seed, "correct", problems, facts)
    assert problems == []
    assert facts["rule"] == "routed" and facts["positions"] == 72
    # room on the passing side
    assert facts["rel_err_quantile"] < 0.6 * TOL
    assert facts["rel_err_max"] < NUMBERS["cap"]
    assert facts["near_tie_margin"] == lfm2_moe.TIE_MARGIN


@pytest.mark.parametrize("name", WRONG)
def test_a_wrong_system_fails_the_routed_rule(weights, monkeypatch, name):
    seed = SEEDS[0]
    sut, keys = wrong_systems(MCFG, weights(seed), monkeypatch)[name]()
    problems, facts = correctness.numbers(sut, keys, seed)
    say(seed, name, problems, facts)
    assert problems, name
    # what catches it is the quantile, with room
    assert facts["rel_err_quantile"] > 1.5 * TOL


def test_what_the_routed_rule_cannot_see(weights, monkeypatch):
    """A state dropped between two prefill chunks ninety tokens before
    the first scored position passes: the conv remembers two tokens, so
    what the drop moves reaches the scored positions only through the
    K/V of two positions among 190. The float32 tests of the chunked
    path (tests/test_lfm2_paths.py) are what catches it."""
    seed = SEEDS[0]
    name = "conv state zeroed at one prefill chunk boundary"
    sut, keys = wrong_systems(MCFG, weights(seed), monkeypatch)[name]()
    problems, facts = correctness.numbers(sut, keys, seed)
    say(seed, name, problems, facts)
    assert problems == []
