"""The idle-share and admission-wait readers on a hand-built ``ctx``: known
gaps between program executions, known spans, one case per group."""
import importlib.util
import json
import os
import types

import pytest

from benchmarks import contract, trace_reduce as tr
from benchmarks.metrics import _host_spans as hs

HERE = os.path.dirname(os.path.abspath(__file__))
LAYOUT = tr.load_layout()
MS = 1_000_000
HOST0 = 1000.0                      # the marker opened at this host time (s)
TRACE0 = 7_000 * MS                 # ... which is this instant on the trace's clock


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def at(ms):
    """Window-relative ms -> ns on the host's monotonic clock."""
    return int(HOST0 * 1e9) + int(ms * MS)


def make_ctx(programs, spans=None, admit=None, window_ms=100, in_step_hole=None):
    """``programs``: (start_ms, end_ms) of each execution, busy throughout but
    for ``in_step_hole``. ``spans``: (name, t0_ms, t1_ms) on the host clock."""
    ops, mods = [], []
    for k, (a, b) in enumerate(programs):
        mods.append([f"jit_decode_multi({k})", TRACE0 + a * MS, (b - a) * MS])
        if in_step_hole and a <= in_step_hole[0] and in_step_hole[1] <= b:
            h0, h1 = in_step_hole
            ops.append([f"fusion.{k}", TRACE0 + a * MS, (h0 - a) * MS])
            ops.append([f"fusion.{k}b", TRACE0 + h1 * MS, (b - h1) * MS])
        else:
            ops.append([f"fusion.{k}", TRACE0 + a * MS, (b - a) * MS])
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["benchmark_window", TRACE0, window_ms * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods}, {"name": "XLA Ops", "events": ops}]},
    ]}
    ctx = types.SimpleNamespace()
    ctx.trace = tr.Reduced(trace, LAYOUT)
    ctx.trace_host = (HOST0, HOST0 + window_ms / 1e3)
    fields = {}
    if spans is not None:
        fields["host_spans"] = tuple(v for n, a, b in spans for v in (n, at(a), at(b)))  # flat
    if admit is not None:
        fields["admit_wait_s"] = tuple(admit)
    ctx.steps = [(HOST0 + 0.05, types.SimpleNamespace(phase="decode", **fields))]
    return ctx


def shares(ctx):
    out = hs.reduce(ctx)
    assert out is not None
    return out


# one 10 ms gap between two programs, [40, 50) of a 100 ms window, and the
# idle before the first program [0, 10) and after the last [90, 100)
PROGRAMS = [(10, 40), (50, 90)]


@pytest.mark.parametrize("name, group", [
    ("admit", "schedule"), ("book", "schedule"), ("reap", "schedule"), ("publish", "schedule"),
    ("emit", "emit"),
    ("yield", "yield"), ("idle", "yield"), ("step", "yield"),
    ("pack", "dispatch"), ("upload", "dispatch"), ("launch", "dispatch"),
])
def test_a_span_over_the_gap_puts_it_in_its_group(name, group):
    got = shares(make_ctx(PROGRAMS, spans=[(name, 38, 52)]))
    assert got[group] == pytest.approx(10.0)         # 10 of 100 ms
    assert got["unattributed"] == pytest.approx(20.0)  # the window's two ends
    assert sum(v for g, v in got.items() if g not in (group, "unattributed")) == pytest.approx(0.0)


@pytest.mark.parametrize("name", ["sync", "fetch"])
def test_a_wait_is_readback_before_the_gap_and_launch_lag_after(name):
    # the wait began while the first program ran: its results are late
    got = shares(make_ctx(PROGRAMS, spans=[(name, 30, 44)]))
    assert got["readback"] == pytest.approx(4.0) and got["dispatch"] == pytest.approx(0.0)
    # the wait began inside the gap: the launch has returned, the device has not started
    got = shares(make_ctx(PROGRAMS, spans=[(name, 46, 60)]))
    assert got["dispatch"] == pytest.approx(4.0) and got["readback"] == pytest.approx(0.0)
    assert got["unattributed"] == pytest.approx(26.0)


def test_an_executor_span_wins_over_the_loop_span_around_it():
    got = shares(make_ctx(PROGRAMS, spans=[("step", 40, 50), ("pack", 42, 45), ("launch", 45, 46)]))
    assert got["dispatch"] == pytest.approx(4.0)     # pack 3 + launch 1
    assert got["yield"] == pytest.approx(6.0)        # step less what is inside it
    assert got["unattributed"] == pytest.approx(20.0)


def test_a_boundary_in_order_and_the_sum():
    spans = [("sync", 35, 41), ("step", 34, 41.5), ("emit", 41.5, 43), ("reap", 43, 43.5),
             ("publish", 43.5, 44), ("yield", 44, 46), ("admit", 46, 46.5), ("book", 46.5, 47),
             ("step", 47, 60), ("pack", 47.5, 49), ("launch", 49, 49.5), ("sync", 49.5, 59)]
    ctx = make_ctx(PROGRAMS, spans=spans, in_step_hole=(60, 62))
    got = shares(ctx)
    assert got["readback"] == pytest.approx(1.0)
    assert got["emit"] == pytest.approx(1.5)
    assert got["schedule"] == pytest.approx(2.0)
    assert got["yield"] == pytest.approx(3.0)        # yield 2, hand-off 0.5 + 0.5
    assert got["dispatch"] == pytest.approx(2.5)     # pack 1.5, launch 0.5, launch lag 0.5
    assert got["unattributed"] == pytest.approx(20.0)
    assert got["in_step"] == pytest.approx(2.0)
    idle = 100.0 * (1.0 - ctx.trace.busy0_s / ctx.trace.window_s)
    assert sum(got.values()) == pytest.approx(idle)
    for g in hs.GROUPS:
        for suffix in (".tput", ".lat"):
            assert reader(f"idle_{g}_share{suffix}")(ctx) == pytest.approx(got[g])


def test_spans_that_meet_no_idle_time_read_zero():
    got = shares(make_ctx(PROGRAMS, spans=[("emit", 20, 30), ("pack", 60, 70)]))
    assert all(got[g] == 0.0 for g in hs.GROUPS if g != "unattributed")
    assert got["unattributed"] == pytest.approx(30.0)
    assert shares(make_ctx(PROGRAMS, spans=[]))["unattributed"] == pytest.approx(30.0)


def test_in_step_idle_is_left_to_itself():
    got = shares(make_ctx(PROGRAMS, spans=[("emit", 0, 100)], in_step_hole=(20, 25)))
    assert got["emit"] == pytest.approx(30.0) and got["in_step"] == pytest.approx(5.0)


ENTRIES = os.path.join(os.path.dirname(HERE), "metrics", "host_spans.per_layer.json")


def entries():
    with open(ENTRIES) as f:
        return json.load(f)


def test_a_program_without_the_fields_reads_none_everywhere():
    ctx = make_ctx(PROGRAMS)                          # steps carry neither field
    for m in entries():
        assert reader(m["name"])(ctx) is None, m["name"]


def test_a_program_with_the_fields_reads_a_number_or_nobody_admitted():
    ctx = make_ctx(PROGRAMS, spans=[], admit=[])
    assert reader("idle_emit_share.lat")(ctx) == 0.0
    assert reader("admit_wait_p50_ms.open")(ctx) is None  # the field is there, nobody was admitted


def test_the_entries_kept_for_the_manifest_fit_it():
    """``host_spans.per_layer.json``: 15 entries a ``benchmark`` PR appends to
    ``BENCHMARK.json``'s ``per_layer``; each has its reader file, the keys the
    manifest's entries have, cells that report what it moves, and no name
    the manifest has already."""
    manifest = contract.load_manifest(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json"))
    mine = entries()
    assert len(mine) == 15 and len({m["name"] for m in mine}) == 15
    have = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]}
    cells = {c["name"] for c in manifest["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells)) for e in manifest["end_to_end"]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["name"] not in have and m["layer"] in layers
        assert set(m["workloads"]) <= reports[m["moves"]]
        assert reader(m["name"])
    per_cell = {c: sum(c in m["workloads"] for m in mine) for c in cells}
    assert per_cell == {"internlm2-longcache-decode": 7, "mistral7b-rag-prefill": 7, "internlm2-chat-steady": 8}


@pytest.mark.parametrize("name, want", [
    ("admit_wait_p50_ms.closed", 20.0), ("admit_wait_p50_ms.open", 20.0), ("admit_wait_p95_ms.open", 86.0)])
def test_admission_wait_percentiles(name, want):
    ctx = make_ctx(PROGRAMS, spans=[], admit=[0.0, 0.010, 0.020, 0.030, 0.100])
    assert reader(name)(ctx) == pytest.approx(want)


def test_no_trace_reads_none():
    ctx = make_ctx(PROGRAMS, spans=[("emit", 38, 52)])
    ctx.trace = None
    assert reader("idle_emit_share.tput")(ctx) is None
