"""``paged_run_chunk_share.tput`` (ISSUE 50) on hand-built windows: the share
of the SUMS (a horizon's eight steps weigh eight times a lone step's),
``None`` and not 0 where the counters say no whole chunk was read, and 0.0
from a program whose ``StepStats`` has no such fields: the driver runs the
parent under this PR's benchmark files, and ``run.py``'s own check refuses a
line that lacks a listed metric (``contract.check_line``; PR 48's reader
learned the same)."""
import json
import os
import types

import pytest

from benchmarks.tests.test_host_spans import reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "paged_run_chunk_share.tput"


def window(*steps):
    """``steps``: (phase, whole, run), or (phase,) for a StepStats without the fields."""
    made = []
    for phase, *counts in steps:
        fields = dict(zip(("paged_chunks_whole", "paged_chunks_run"), counts))
        made.append((1000.0 + len(made), types.SimpleNamespace(phase=phase, **fields)))
    return types.SimpleNamespace(steps=made, trace=None)


def test_the_share_of_the_sums_not_the_mean_of_the_shares():
    # a horizon of 8 steps over 24 rows of 65 whole chunks in one layer, 64 of
    # 65 runs; a lone step over a pool that churn has shuffled: 10 of 50;
    # mixed steps and prefills count nothing (their rows are the ragged launch's)
    ctx = window(("decode", 8 * 24 * 65, 8 * 24 * 64), ("decode", 50, 10),
                 ("prefill", 0, 0), ("mixed", 0, 0))
    assert reader(NAME)(ctx) == pytest.approx(100.0 * (12288 + 10) / (12480 + 50))
    assert reader(NAME)(ctx) != pytest.approx((100.0 * 64 / 65 + 20.0) / 2)
    assert reader(NAME)(window(("decode", 400, 400), ("decode", 450, 450))) == 100.0
    assert reader(NAME)(window(("decode", 400, 0))) == 0.0


def test_no_whole_chunk_reads_none_not_zero():
    assert reader(NAME)(window(("decode", 0, 0), ("prefill", 0, 0))) is None
    assert reader(NAME)(window(("decode", None, None))) is None


def test_a_parent_shaped_window_reads_zero_and_does_not_raise():
    got = reader(NAME)(window(("decode",), ("prefill",), ("mixed",)))
    assert got == 0.0 and got is not None
    assert reader(NAME)(window()) == 0.0


def test_the_entry_is_listed_for_the_cells_whose_decode_rows_fill_a_chunk():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    roofline = next(m for m in manifest["per_layer"] if m["name"] == "paged_decode_attention_roofline")
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "output_tokens_per_s",
        # the roofline's cells but the wide-chat cell, whose rows hold 22 pages
        # on average and seldom a whole chunk of 64: None there would break the line
        "workloads": [w for w in roofline["workloads"] if w != "falconh1-chat-wide"],
    }
    assert callable(reader(NAME))
