"""``mixed_chained_share.tput`` / ``.tpot`` (ISSUE 42) on hand-built windows:
one shaped as the change makes them (``StepStats.mixed_chained`` a boolean on
mixed steps, ``None`` elsewhere) and one shaped as the parent's (no such
field), where the reader has to give 0.0 and not ``None``: the driver runs the
parent under this PR's benchmark files, and a listed metric that is missing
from the line refuses the run."""
import json
import os
import types

import pytest

from benchmarks import contract
from benchmarks.tests.test_host_spans import reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ["mixed_chained_share.tput", "mixed_chained_share.tpot"]


def window(*steps):
    """``steps``: (phase, mixed_chained) or (phase,) for a StepStats without the field."""
    made = []
    for phase, *flag in steps:
        fields = {"mixed_chained": flag[0]} if flag else {}
        made.append((1000.0 + len(made), types.SimpleNamespace(phase=phase, **fields)))
    return types.SimpleNamespace(steps=made, trace=None)


@pytest.mark.parametrize("name", NAMES)
def test_a_change_shaped_window_reads_the_share_of_its_mixed_steps(name):
    ctx = window(("decode", None), ("mixed", False), ("mixed", True), ("mixed", True),
                 ("prefill", None), ("mixed", True), ("decode", None))
    assert reader(name)(ctx) == pytest.approx(75.0)
    assert reader(name)(window(("mixed", True), ("mixed", True))) == 100.0
    assert reader(name)(window(("mixed", False), ("decode", None))) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_shaped_window_reads_zero_not_none(name):
    ctx = window(("decode",), ("mixed",), ("mixed",), ("prefill",))
    got = reader(name)(ctx)
    assert got == 0.0 and got is not None
    # and so does a window that ran no mixed step at all
    assert reader(name)(window(("decode",), ("prefill", None))) == 0.0


def test_the_entries_are_the_manifests_last_and_list_the_cells_that_run_mixed_steps():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    contract.check_manifest(manifest) if hasattr(contract, "check_manifest") else None
    tput, tpot = manifest["per_layer"][-2:]
    assert [tput["name"], tpot["name"]] == NAMES
    for entry, moves in ((tput, "output_tokens_per_s"), (tpot, "tpot_p95_ms")):
        assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
            "%", "higher", "program_counter", "step programs", moves)
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(tput["workloads"]) | set(tpot["workloads"]) == cells - {"glm52-longdoc-sessions"}
    assert tpot["workloads"] == ["internlm2-chat-steady"]
    # the reader is found by the metric's name, as run.py finds it
    for name in NAMES:
        assert callable(reader(name))
