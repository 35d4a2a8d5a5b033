"""The readers of the cell whose end-to-end metric is a job's
turnaround, each on a synthetic ``Reading``; and ``run.metrics_for``:
a per-layer metric that lists no cell goes wherever the end-to-end
metric it moves is reported, and nowhere else."""

import importlib
import json
from pathlib import Path

import pytest

from perfbench import run

from .test_sched_readers import hist, reading

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def job(log, submitted, ended, status="SUCCEEDED", warm=False):
    log.add_job({"job_id": "j", "submitted": submitted, "ended": ended,
                 "status": status, "rows": 64, "warm": warm, "problems": [],
                 "length_rows": 0})


def test_turnaround_is_the_median_of_jobs_that_ended_in_the_window():
    r = reading()
    read = importlib.import_module("perfbench.e2e_metrics.job_turnaround_s").read
    assert read(r) is None
    job(r.log, 20.0, 60.0, warm=True)            # a warm job: not in it
    job(r.log, 85.0, 99.0)                       # ended before the window
    job(r.log, 128.0, None, status=None)         # still running
    job(r.log, 128.0, 139.0, status="CANCELLED")
    assert read(r) is None
    job(r.log, 85.0, 127.5)                      # submitted in the lead-in
    assert read(r) == pytest.approx(42.5)
    job(r.log, 100.0, 110.0)
    job(r.log, 110.0, 130.0)
    assert read(r) == pytest.approx(20.0)


def test_constraint_build_share_clips_job_scope_spans_to_the_window():
    read = importlib.import_module(
        "perfbench.layer_metrics.constraint_build_share").read
    assert read(reading()) is None
    spans = [
        ("constraint_prep", 85.0, 97.0, {"scope": "job", "thread": "submit"}),
        ("constraint_compile", 97.0, 108.0, {"scope": "job", "rows": 64}),
        ("constraint_compile", 109.0, 109.5, {"rows": 1}),      # a row's
        ("constraint_prep", 127.0, 137.0, {"scope": "job", "thread": "submit"}),
        ("constraint_compile", 137.0, 147.0, {"scope": "job"}),
        ("fsm_plan", 110.0, 120.0, {"scope": "job"}),
    ]
    # 8 + 10 + 3 of 40 s
    assert read(reading(spans=spans)) == pytest.approx(100 * 21.0 / 40)
    assert read(reading(spans=spans[:1])) is None


def test_the_renamed_readers_read_what_their_originals_read():
    after = hist(batch_build=(100, 2.0), accept=(100, 4.0), fsm_mask=(5, 0.5),
                 fsm_plan=(5, 1.5))
    r = reading({}, after, tokens=2000, seconds=40.0)
    layer = "perfbench.layer_metrics."
    share = importlib.import_module(layer + "turnaround_sched_host_share").read(r)
    assert share == importlib.import_module(layer + "sched_host_share").read(r)
    assert share == pytest.approx(100 * 8.0 / 40)
    burst = importlib.import_module(layer + "decode_burst_tokens_per_s").read(r)
    assert burst == pytest.approx(2000 / 39.0)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_metrics_for_follows_the_end_to_end_metric(cell):
    e2e = {m["name"] for m in run.metrics_for(BENCH, cell, "end_to_end")}
    layer = run.metrics_for(BENCH, cell, "per_layer")
    for m in BENCH["per_layer"]:
        here = cell["name"] in m.get("workloads", [cell["name"]])
        assert (m in layer) == (here and m["moves"] in e2e), m["name"]
    if "job_turnaround_s" in e2e:
        assert "out_tokens_per_s_per_chip" not in e2e
        assert {m["name"] for m in layer} >= {
            "fsm_host_us_per_token", "decode_burst_tokens_per_s",
            "constraint_build_share", "turnaround_sched_host_share"}
