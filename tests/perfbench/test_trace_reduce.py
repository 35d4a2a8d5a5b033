"""trace_reduce on the small recorded trace kept in the package, against
numbers worked by hand."""

import copy
import json
from pathlib import Path

import pytest

from perfbench import trace_reduce as tr

TRACE = json.loads(
    (Path(tr.__file__).parent / "data" / "recorded_trace.json").read_text()
)


@pytest.fixture()
def reduced():
    return tr.reduce_trace(TRACE)


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert tr.total(tr.union([(0, 10), (2, 3), (8, 12)])) == 12


def test_busy_is_the_union_not_the_sum(reduced):
    # [0,1000] and [1500,2000] u [1800,2200] = 1000 + 700 ns
    assert reduced["busy_s"] == pytest.approx(1700e-9)
    assert reduced["window_s"] == pytest.approx(2500e-9)
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.32)


def test_idle_gaps(reduced):
    assert reduced["gaps_ns"] == [(1000, 1500), (2200, 2500)]


def test_module_sums_and_runs(reduced):
    assert tr.module_seconds(reduced, r"decode") == (pytest.approx(1000e-9), 1.0)
    assert tr.module_seconds(reduced, r"prefill") == (pytest.approx(500e-9), 1.0)
    assert tr.module_seconds(reduced, r"nothing") == (0.0, 0.0)


def test_self_time_of_nested_ops(reduced):
    # the while covers its four body ops exactly: no self time left
    assert reduced["op_s"]["while"] == 0.0
    assert reduced["op_s"]["fusion"] == pytest.approx(1200e-9)
    # overlapping but not nested: each keeps its whole duration
    assert reduced["op_s"]["copy"] == pytest.approx(400e-9)


def test_overlapping_ops_case():
    evs = [["a", 0, 100], ["b", 10, 20], ["c", 15, 5], ["d", 90, 30]]
    # c nests in b, b in a; d starts in a and ends after it: not nested
    assert tr.self_times(evs) == [80.0, 15.0, 5.0, 30.0]


def test_classes(reduced):
    assert reduced["class_s"]["pallas"] == pytest.approx(200e-9)
    assert reduced["class_s"]["collective"] == pytest.approx(100e-9)


def test_top_ops_order(reduced):
    names = [name for name, _s in tr.top_ops(reduced, top=2)]
    assert names == ["fusion", "copy"]


def test_two_identical_chips_average_to_one(reduced):
    two = copy.deepcopy(TRACE)
    two["devices"]["/device:TPU:1"] = copy.deepcopy(
        two["devices"]["/device:TPU:0"]
    )
    both = tr.reduce_trace(two)
    assert both["n_devices"] == 2
    assert both["busy_s"] == pytest.approx(reduced["busy_s"])
    assert both["class_s"] == pytest.approx(reduced["class_s"])
    assert both["module_s"]["jit__decode_multi_jit"]["runs"] == 1.0


def test_gaps_go_to_the_host_span_that_covers_them(reduced):
    offset = tr.mono_offset_ns(TRACE)
    assert offset == 1000000000
    spans = [
        ("accept", 1.0000010, 1.0000016),      # covers the first gap's middle
        ("decode_window", 1.0, 1.000003),      # covers it too, but longer
    ]
    got = dict(tr.attribute_gaps(reduced["gaps_ns"], offset, spans))
    assert got["accept"] == pytest.approx(500e-9)
    assert got["decode_window"] == pytest.approx(300e-9)
    none = dict(tr.attribute_gaps(reduced["gaps_ns"], None, spans))
    assert none == {"unattributed": pytest.approx(800e-9)}


def test_window_falls_back_to_the_events_without_sync_marks():
    bare = {"devices": TRACE["devices"], "sync": []}
    assert tr.window_of(bare) == (0, 2200)


def test_module_and_op_keys():
    assert tr.module_key("jit__decode_multi_jit(7012345)") == "jit__decode_multi_jit"
    assert tr.op_key("fusion.123") == "fusion"
    assert tr.op_key("all-reduce.3") == "all-reduce"


def test_an_hlo_line_is_split_into_name_and_opcode():
    name, text = tr.split_hlo(
        "%fusion.7 = bf16[64,9728]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[36,2560,9728]"
        "{2,1,0} %all-reduce.3, s32[] %x), kind=kLoop, calls=%fused_computation.4"
    )
    assert (name, text) == ("fusion.7", "fusion")
    classes = tr.load_classes()
    # reading a collective's result does not make a fusion a collective
    assert tr.classify(name, text, classes) is None
    name, text = tr.split_hlo(
        "%paged_decode_attention.8 = bf16[64,32,128]{2,1,0} custom-call(s32[1024]{0} "
        '%a, bf16[579,64,1024]{2,1,0} %b), custom_call_target="tpu_custom_call", '
        "operand_layout_constraints={s32[1024]{0}}"
    )
    assert (name, text) == ("paged_decode_attention.8", "custom-call tpu_custom_call")
    assert tr.classify(name, text, classes) == "pallas"
    name, text = tr.split_hlo(
        "%all-reduce.5 = (f32[64]{0}, bf16[64,1,4096]{2,0,1}) all-reduce(f32[64]{0} %p)"
    )
    assert tr.classify(name, text, classes) == "collective"
    assert tr.split_hlo("perfbench_sync") == ("perfbench_sync", "")
