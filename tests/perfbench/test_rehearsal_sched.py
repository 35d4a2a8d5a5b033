"""The CPU rehearsal prints the scheduler's program_counter metrics for
the cells that stand for the classify and the generate cell (the
device_trace one is skipped off the chip, like every other)."""

import pytest

from .test_rehearsal import result_of, run

SCHED = {"sched_host_share", "sched_other_share", "decode_batch_occupancy"}


@pytest.mark.parametrize("cell,expect", [
    # its end-to-end metric is the job's turnaround, so it prints the
    # scheduler's share under the name of what it moves there
    ("tiny.classify-jobs", {"turnaround_sched_host_share", "fsm_host_us_per_token"}),
    ("tiny.generate-jobs", SCHED),
])
def test_rehearsal_prints_the_scheduler_metrics(cell, expect):
    result = result_of(run(
        "--workload", cell, "--seed", str(2**31 + 7), "--seconds", "10",
        "--trace", "1", "--cpu-rehearsal",
    ))
    assert result["correct"] is True and result["failed"] == 0
    assert expect <= set(result["metrics"])
    values = {k: result["metrics"][k]["value"] for k in expect}
    assert all(v >= 0 for v in values.values())
    if cell == "tiny.generate-jobs":
        assert 0 < values["sched_host_share"] <= 100
        assert values["sched_other_share"] < 5
        assert 0 < values["decode_batch_occupancy"] <= 100
        assert "fsm_host_us_per_token" not in result["metrics"]
    else:
        assert 0 < values["turnaround_sched_host_share"] <= 100
        assert not SCHED & set(result["metrics"])
