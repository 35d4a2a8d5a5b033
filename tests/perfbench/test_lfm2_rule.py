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


# -- the chip configuration's own values (PR 56) ------------------------------

CHIP_FILE = json.loads((Path(correctness.__file__).parent
                        / "configs/lfm2-24b-a2b-l10-v5e1.json").read_text())


def test_the_chip_files_values_are_what_the_check_allows():
    """Sized from 115 seeds on the chip (the file's ``numbers.why``): the
    low quantile moved off the range's old end, the cap held at a high
    quantile of a run's 288 positions in place of their maximum."""
    spec = correctness.routed_spec(CHIP_FILE)
    assert (spec["sequences"], spec["quantile"], spec["cap"],
            spec["cap_quantile"]) == (32, 0.05, 0.5, 0.99)
    assert spec["quantile"] == correctness.QUANTILE_RANGE[0]
    assert spec["cap_quantile"] == correctness.MIN_CAP_QUANTILE
    assert spec["sequences"] >= correctness.CAP_QUANTILE_SEQUENCES
    for word in ("115 seeds", "1098603819", "float8_e4m3", "0.99 quantile"):
        assert word in spec["why"]


@pytest.mark.parametrize("name", ["correct", "weights through float8_e4m3"])
def test_the_chip_files_rule_on_this_preset(weights, monkeypatch, name):
    """The file's quantile, cap and cap_quantile over its 32 sequences,
    on the CPU preset: the correct bf16 system passes, and the control
    (the precision below) fails BOTH limits, as it does on the chip."""
    seed = SEEDS[0]
    numbers = {k: CHIP_FILE["numbers"][k]
               for k in ("sequences", "quantile", "cap", "cap_quantile", "why")}
    if name == "correct":
        sut, keys = ForwardSystem(MCFG, weights(seed)), published_keys(MCFG)
    else:
        sut, keys = wrong_systems(MCFG, weights(seed), monkeypatch)[name]()
    problems, facts = correctness.numbers(sut, dict(keys, numbers=numbers), seed)
    print(f"{name}: 0.05 quantile {facts['rel_err_quantile']:.4f}, 0.99 "
          f"quantile {facts['rel_err_cap_quantile']:.4f}, largest "
          f"{facts['rel_err_max']:.4f} -> {len(problems)} problem(s)")
    assert facts["positions"] == 288 and facts["cap_quantile"] == 0.99
    if name == "correct":
        assert problems == []
        assert facts["rel_err_quantile"] < 0.8 * TOL
        assert facts["rel_err_cap_quantile"] < 0.8 * numbers["cap"]
    else:
        assert len(problems) == 2
        assert facts["rel_err_quantile"] > 1.5 * TOL
        assert facts["rel_err_cap_quantile"] > numbers["cap"]
