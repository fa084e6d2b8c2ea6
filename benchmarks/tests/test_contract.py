import json
import os

import pytest

from benchmarks import contract

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = contract.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
CELL = MANIFEST["workloads"][0]["name"]


def good_line(trace: bool):
    metrics = {m["name"]: {"value": 1.5, "unit": m["unit"]}
               for m in contract.metrics_of(MANIFEST, CELL, trace)}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 13_900_000_000}
    line = {"correct": True, "attempted": 100, "failed": 0, "metrics": metrics, "device": device}
    if trace:
        device.update(window_s=5.0, busy_s=3.2)
        line["breakdown"] = {"device_ops": [["fusion.1", 1.0]], "idle_gaps": [["between_steps", 0.5]]}
    return line


@pytest.mark.parametrize("trace", [False, True])
def test_a_good_line_passes_in_each_mode(trace):
    line = good_line(trace)
    assert contract.check_line(MANIFEST, CELL, trace, line) == []
    assert json.loads(contract.dumps(line)) == line


def _first_metric(trace):
    return contract.metrics_of(MANIFEST, CELL, trace)[0]["name"]


@pytest.mark.parametrize("trace", [False, True])
def test_a_missing_metric_is_refused(trace):
    line = good_line(trace)
    del line["metrics"][_first_metric(trace)]
    assert any("missing" in b for b in contract.check_line(MANIFEST, CELL, trace, line))


@pytest.mark.parametrize("value", [float("nan"), float("inf"), None, "1.0", True])
def test_a_value_that_is_no_finite_number_is_refused(value):
    line = good_line(False)
    line["metrics"][_first_metric(False)]["value"] = value
    assert contract.check_line(MANIFEST, CELL, False, line)
    if isinstance(value, float):
        with pytest.raises(ValueError):
            contract.dumps(line)


def test_a_wrong_unit_is_refused():
    line = good_line(False)
    line["metrics"][_first_metric(False)]["unit"] = "seconds"
    assert any("unit" in b for b in contract.check_line(MANIFEST, CELL, False, line))


@pytest.mark.parametrize("busy,window", [(0.0, 5.0), (5.1, 5.0), (-1.0, 5.0), (float("nan"), 5.0)])
def test_busy_outside_the_window_is_refused(busy, window):
    line = good_line(True)
    line["device"].update(busy_s=busy, window_s=window)
    assert any("busy_s" in b for b in contract.check_line(MANIFEST, CELL, True, line))


def test_a_traced_line_needs_window_and_busy():
    line = good_line(True)
    del line["device"]["busy_s"]
    assert any("busy_s" in b for b in contract.check_line(MANIFEST, CELL, True, line))


def test_other_keys_and_other_metrics_are_refused():
    line = good_line(False)
    line["not_compared"] = []
    assert contract.check_line(MANIFEST, CELL, False, line)
    line = good_line(False)
    line["metrics"]["made_up"] = {"value": 1.0, "unit": "ms"}
    assert contract.check_line(MANIFEST, CELL, False, line)


def test_every_cell_reports_setup_one_more_end_to_end_and_a_layer_metric():
    for cell in MANIFEST["workloads"]:
        e2e = [m["name"] for m in contract.metrics_of(MANIFEST, cell["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = contract.metrics_of(MANIFEST, cell["name"], True)
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (cell["name"], m["name"])


def test_every_listed_metric_has_a_reader_file():
    here = os.path.join(ROOT, "benchmarks", "metrics")
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert os.path.exists(os.path.join(here, m["name"] + ".py")), m["name"]
