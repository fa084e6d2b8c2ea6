"""``dsa_index_run_chunk_share.tput`` (ISSUE 48) on hand-built windows: the
share of the SUMS (a horizon's eight steps weigh eight times a lone step's),
``None`` and not 0 where the counters say no whole chunk was read, and 0.0
from a program whose ``StepStats`` has no such fields: the driver runs the
parent under this PR's benchmark files, and ``run.py``'s own check refuses a
line that lacks a listed metric (``contract.check_line``; PR 42's reader
learned the same)."""
import json
import os
import types

import pytest

from benchmarks.tests.test_host_spans import reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "dsa_index_run_chunk_share.tput"


def window(*steps):
    """``steps``: (phase, whole, run), or (phase,) for a StepStats without the fields."""
    made = []
    for phase, *counts in steps:
        fields = dict(zip(("dsa_index_chunks_whole", "dsa_index_chunks_run"), counts))
        made.append((1000.0 + len(made), types.SimpleNamespace(phase=phase, **fields)))
    return types.SimpleNamespace(steps=made, trace=None)


def test_the_share_of_the_sums_not_the_mean_of_the_shares():
    # a horizon of 8 steps over 8 tables of 25 chunks in 2 selecting layers,
    # 24 of 25 runs; a lone step whose pool churn has shuffled: 10 of 50
    ctx = window(("decode", 8 * 8 * 25 * 2, 8 * 8 * 24 * 2), ("decode", 50, 10),
                 ("prefill", None, None))
    assert reader(NAME)(ctx) == pytest.approx(100.0 * (3072 + 10) / (3200 + 50))
    assert reader(NAME)(ctx) != pytest.approx((96.0 + 20.0) / 2)
    assert reader(NAME)(window(("decode", 400, 400), ("mixed", 450, 450))) == 100.0
    assert reader(NAME)(window(("decode", 400, 0))) == 0.0


def test_no_whole_chunk_reads_none_not_zero():
    assert reader(NAME)(window(("decode", 0, 0), ("prefill", None, None))) is None
    assert reader(NAME)(window(("prefill", None, None))) is None


def test_a_parent_shaped_window_reads_zero_and_does_not_raise():
    got = reader(NAME)(window(("decode",), ("prefill",), ("mixed",)))
    assert got == 0.0 and got is not None
    assert reader(NAME)(window()) == 0.0


def test_the_entry_is_the_manifests_last_and_lists_the_long_document_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "output_tokens_per_s",
        "workloads": ["glm52-longdoc-sessions"],
    }
    assert callable(reader(NAME))
