import json
import os

import pytest

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
LAYOUT = tr.load_layout()
MS = 1_000_000


def synthetic(ops0, modules0=(), ops1=None, marker=(0, 100 * MS)):
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["benchmark_window", marker[0], marker[1] - marker[0]]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [list(e) for e in modules0]},
            {"name": "XLA Ops", "events": [list(e) for e in ops0]},
            {"name": "Steps", "events": [["0", 0, 100 * MS]]},
        ]},
    ]
    if ops1 is not None:
        planes.append({"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [list(e) for e in ops1]}]})
    return {"planes": planes}


def test_overlapping_events_are_merged_not_summed():
    t = synthetic([("a", 10 * MS, 20 * MS), ("b", 20 * MS, 20 * MS), ("c", 60 * MS, 5 * MS)])
    r = tr.Reduced(t, LAYOUT)
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.035)          # [10,40) and [60,65), not 45
    assert 0 < r.busy_s <= r.window_s


def test_events_are_clipped_to_the_marker_window():
    t = synthetic([("a", -50 * MS, 60 * MS), ("b", 90 * MS, 500 * MS)], marker=(0, 100 * MS))
    r = tr.Reduced(t, LAYOUT)
    assert r.busy_s == pytest.approx(0.020)          # [0,10) and [90,100)
    t = synthetic([("a", 0, 1000 * MS)])
    assert tr.Reduced(t, LAYOUT).busy_s == pytest.approx(0.100)   # never above the window


def test_only_the_op_line_counts_and_devices_are_averaged():
    t = synthetic([("a", 0, 10 * MS)], modules0=[("jit_x(1)", 0, 90 * MS)], ops1=[("a", 0, 30 * MS)])
    r = tr.Reduced(t, LAYOUT)
    assert r.n_devices == 2
    assert r.busy_s_per_device == pytest.approx([0.010, 0.030])
    assert r.busy_s == pytest.approx(0.020)


def test_kernel_time_by_name_and_program_durations():
    ops = [("paged_decode_attention.3", 0, 4 * MS), ("fusion.7", 4 * MS, 1 * MS),
           ("paged_decode_attention.3", 10 * MS, 6 * MS)]
    mods = [("jit_decode_multi(123)", 1 * MS, 16 * MS), ("jit_prefill(5)", 30 * MS, 8 * MS),
            ("jit_decode_multi(123)", 95 * MS, 20 * MS)]    # the last is cut by the window's edge
    r = tr.Reduced(synthetic(ops, mods), LAYOUT)
    assert r.op_seconds("paged_decode_attention") == pytest.approx(0.010)
    assert r.op_count("paged_decode_attention") == 2
    assert r.module_durations_s(r"^jit_decode_multi\b") == pytest.approx([0.016])
    assert r.module_durations_s(r"^jit_(prefill|mixed_step)\b") == pytest.approx([0.008])
    assert r.top_ops(1) == [["paged_decode_attention", pytest.approx(0.010)]]   # layers under one name


def test_a_wrapper_that_spans_its_body_is_not_busy_time():
    hlo = "%while.7 = (s32[], bf16[64,16]{1,0}) while(%tuple.3), condition=%cond, body=%body"
    assert tr.short_name(hlo) == "while.7" and tr.short_name("fusion.3") == "fusion.3"
    assert tr.stem("paged_decode_attention.263") == "paged_decode_attention" and tr.stem("copy") == "copy"
    ops = [("while.7", 0, 90 * MS), ("fusion.1", 10 * MS, 10 * MS), ("fusion.2", 40 * MS, 10 * MS),
           ("conditional.1", 60 * MS, 20 * MS), ("fusion.3", 65 * MS, 5 * MS)]
    assert sorted(n for n, _, _ in tr.leaves(ops)) == ["fusion.1", "fusion.2", "fusion.3"]
    r = tr.Reduced(synthetic(ops), LAYOUT)
    assert r.busy_s == pytest.approx(0.025)
    assert [n for n, _ in r.top_ops()] == ["fusion"]


def test_idle_time_is_classed_from_outside():
    ops = [("a", 10 * MS, 10 * MS), ("b", 25 * MS, 5 * MS), ("c", 50 * MS, 10 * MS)]
    mods = [("jit_x(1)", 10 * MS, 20 * MS), ("jit_x(1)", 50 * MS, 10 * MS)]
    r = tr.Reduced(synthetic(ops, mods), LAYOUT)
    idle = dict(r.idle_by_class(in_flight=[(0, 70 * MS)]))
    assert idle["in_step"] == pytest.approx(0.005)            # [20,25)
    assert idle["between_steps"] == pytest.approx(0.040)      # [0,10) + [30,50) + [60,70)
    assert idle["no_request"] == pytest.approx(0.030)         # [70,100)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy0_s)


def test_a_trace_without_a_device_event_raises():
    with pytest.raises(tr.NoDeviceEvents):
        tr.Reduced(synthetic([]), LAYOUT)
    with pytest.raises(tr.NoDeviceEvents):
        tr.Reduced(synthetic([("a", 500 * MS, 10 * MS)]), LAYOUT)     # outside the window
    with pytest.raises(tr.NoDeviceEvents):
        tr.Reduced({"planes": [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["benchmark_window", 0, 10]]}]}]}, LAYOUT)
    no_marker = synthetic([("a", 0, 10 * MS)])
    no_marker["planes"][0]["lines"][0]["events"] = []
    with pytest.raises(tr.NoDeviceEvents):
        tr.Reduced(no_marker, LAYOUT)


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]
    assert tr.union_ns([(0, 10), (5, 15), (20, 21)]) == 16
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


RECORDED = os.path.join(HERE, "data", "tpu_v5e_trace_small.json")


def test_the_recorded_tpu_trace_reduces_to_the_numbers_read_off_it_by_hand():
    """80 ms of a real TPU v5 lite trace (PR 23, mistral7b-rag-prefill, kept by
    ``trace_reduce.dump(save_small=...)``): mixed steps, with the unified
    ragged kernel and the matmul fusions on the op line."""
    with open(RECORDED) as f:
        trace = json.load(f)
    r = tr.Reduced(trace, LAYOUT)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.080)
    assert r.busy_s == pytest.approx(0.067775835, rel=1e-6)
    assert 0 < r.busy_s <= r.window_s
    assert len(r.ops) == 1542
    assert r.module_durations_s(r"^jit_(prefill|mixed_step)\b", whole_only=False)
    assert r.op_seconds(r"ragged_paged_attention") == pytest.approx(0.038229808, rel=1e-6)
    assert [n for n, _ in r.top_ops(2)] == ["ragged_paged_attention", "fusion"]
    idle = dict(r.idle_by_class())
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy0_s)
    assert idle["between_steps"] > idle["in_step"] > 0
