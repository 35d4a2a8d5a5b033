"""The reader of what the decode dispatches yield, on a hand-made
``Reading``: a value where the registry has the counters, 0.0 where
they exist and did not move, nothing where the program has no such
counter (the parent commit); its entry in ``BENCHMARK.json``; and the
rehearsals: the generate cell prints it, the classify cell does not."""

import json
from pathlib import Path

import pytest

from perfbench import run as harness

from .test_rehearsal import result_of, run
from .test_sched_readers import reader, reading

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = "decode_row_steps_kept_share"
STEPS = "sutro_sched_row_steps_total"
COMMITTED = "sutro_sched_tokens_committed_total"
LOST = "sutro_sched_row_steps_lost_total"
# the cells the entry was added with; later cells may join them
GENERATE = {
    "qwen3-4b.generate-jobs", "qwen3-8b-tp4.generate-jobs",
    "lfm2-24b-a2b-l10.generate-short-jobs",
    "granite-4.0-h-micro.generate-short-jobs",
    "mellum2-12b-a2.5b-l8.generate-long-prompt-jobs",
}


def registry(steps=None, committed=None, lost=None):
    """A registry snapshot with the three counters declared (a program
    that has them exports them with no series before a dispatch)."""
    return {
        STEPS: {"series": dict(steps or {})},
        COMMITTED: {"series": dict(committed or {})},
        LOST: {"series": dict(lost or {})},
    }


# a window: what was there before it, and at its end. Over the window:
# 62 speculative windows of 8 rows x 8 steps, 3 verify forwards of 8 x 17
BEFORE = registry(
    steps={"window": 640, "pipelined": 512},
    committed={"window": 80, "pipelined": 500},
    lost={"window,rejected": 560, "pipelined,finished": 12},
)
AFTER = registry(
    steps={"window": 640 + 3968, "fastforward": 408, "pipelined": 512},
    committed={"window": 80 + 360, "fastforward": 141, "pipelined": 500},
    lost={
        "window,rejected": 560 + 3500, "window,finished": 108,
        "fastforward,plan_short": 240, "fastforward,diverged": 19,
        "fastforward,rejected": 8, "pipelined,finished": 12,
    },
)


def test_a_program_without_the_counters_reads_as_nothing():
    """The parent commit: its registry has iterations and rows and no
    counter of row-steps, so the metric is left out of its line."""
    other = {"sutro_sched_iterations_total": {"series": {"window": 9.0}}}
    assert reader(NAME).read(reading({}, {})) is None
    assert reader(NAME).read(reading(other, other)) is None


def test_the_share_is_read_from_what_the_window_added():
    assert reader(NAME).read(reading(BEFORE, AFTER)) == pytest.approx(
        100 * 501 / 4376
    )


def test_nothing_lost_is_a_number_not_nothing():
    """A generate window: full fused windows, no row ended in them."""
    after = registry(steps={"pipelined": 4096}, committed={"pipelined": 4096})
    assert reader(NAME).read(reading(registry(), after)) == 100.0


def test_counters_that_did_not_move_read_zero():
    assert reader(NAME).read(reading(AFTER, AFTER)) == 0.0


def test_kept_and_lost_add_up_to_the_row_steps():
    """The hand-made window keeps the program's invariant, so that the
    share above is a share of one whole."""
    kept = reader(NAME)
    r = reading(BEFORE, AFTER)
    assert kept.gained(r, STEPS) == kept.gained(r, COMMITTED) + kept.gained(
        r, LOST
    )


def test_the_entry_agrees_with_the_reader_and_lists_generate_cells():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    mod = reader(NAME)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES
    ) == ("%", "higher", "program_counter", "scheduler",
          "out_tokens_per_s_per_chip")
    assert set(entry["workloads"]) >= GENERATE
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == mod.MOVES)
    assert set(entry["workloads"]) <= set(e2e["workloads"])


@pytest.mark.parametrize("cell", sorted(GENERATE))
def test_the_harness_asks_the_cell_for_the_share(cell):
    wanted = harness.metrics_for(
        BENCH, next(w for w in BENCH["workloads"] if w["name"] == cell),
        "per_layer",
    )
    assert NAME in {m["name"] for m in wanted}


def test_the_generate_rehearsal_prints_the_kept_share():
    result = result_of(run(
        "--workload", "tiny.generate-jobs", "--seed", str(2**31 + 11),
        "--seconds", "10", "--trace", "1", "--cpu-rehearsal",
    ))
    assert result["correct"] is True and result["failed"] == 0
    assert 0 < result["metrics"][NAME]["value"] <= 100


def test_the_classify_rehearsal_leaves_it_out():
    """The classify cell reports a turnaround, which this share does
    not move: its line is as it was."""
    result = result_of(run(
        "--workload", "tiny.classify-jobs", "--seed", str(2**31 + 11),
        "--seconds", "10", "--trace", "1", "--cpu-rehearsal",
    ))
    assert result["correct"] is True and result["failed"] == 0
    assert NAME not in result["metrics"]
