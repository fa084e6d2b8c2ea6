"""The readers PR 35 lists, on hand-built contexts: the ``yield`` group cut
by ``submit`` and ``deliver`` spans, a request's ``submit`` time, the host's
gap before a chunk-carrying step and before a horizon, and the two counters'
readers; each also on a parent-like context whose ``StepStats`` lack the new
field, where every one reads a finite number."""
import json
import math
import os
import types

import pytest

from benchmarks import contract
from benchmarks.metrics import _host_spans as hs
from benchmarks.metrics import _request_spans as rs
from benchmarks.metrics import _step_gaps
from benchmarks.tests.test_host_spans import PROGRAMS, at, make_ctx, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["idle_submit_share", "idle_deliver_share", "idle_yield_unnamed_share"]


def with_requests(ctx, quads=None):
    """``quads``: (name, t0_ms, t1_ms, request_id); ``None`` leaves the field
    out, as a program that predates it does."""
    if quads is not None:
        ctx.steps[0][1].request_spans = tuple(
            v for n, a, b, rid in quads for v in (n, at(a), at(b), rid))
    return ctx


def parts(ctx):
    out = rs.reduce(ctx)
    assert out is not None
    return out


# PROGRAMS leaves one 10 ms gap, [40, 50) of a 100 ms window
def test_a_submit_span_inside_a_yield_names_that_part_of_it():
    ctx = with_requests(make_ctx(PROGRAMS, spans=[("yield", 38, 52)]), [("submit", 42, 46, "r1")])
    got = parts(ctx)
    assert got["submit"] == pytest.approx(4.0) and got["deliver"] == pytest.approx(0.0)
    assert got["unnamed"] == pytest.approx(6.0)
    assert got["yield"] == pytest.approx(hs.idle_share(ctx, "yield")) == pytest.approx(10.0)


@pytest.mark.parametrize("loop_span", ["yield", "idle", "step"])
def test_every_span_of_the_yield_group_is_cut(loop_span):
    ctx = with_requests(make_ctx(PROGRAMS, spans=[(loop_span, 38, 52)]),
                        [("deliver", 41, 42, "r1"), ("deliver", 44, 45.5, "r2")])
    got = parts(ctx)
    assert got["deliver"] == pytest.approx(2.5) and got["unnamed"] == pytest.approx(7.5)


def test_submit_wins_where_a_deliver_is_held_open_beside_it():
    # r1's caller awaits behind its yield: its deliver stays open while r2 is submitted
    ctx = with_requests(make_ctx(PROGRAMS, spans=[("yield", 40, 50)]),
                        [("submit", 43, 46, "r2"), ("deliver", 41, 48, "r1"), ("deliver", 47, 49, "r3")])
    got = parts(ctx)
    assert got["submit"] == pytest.approx(3.0)
    assert got["deliver"] == pytest.approx(5.0)     # [41, 43) + [46, 49): overlaps counted once
    assert got["unnamed"] == pytest.approx(2.0)


def test_only_the_yield_group_is_cut():
    """Idle time under a wait, under the executor's spans or under ``emit``
    keeps its group whatever request ran beside it."""
    spans = [("sync", 35, 42), ("emit", 42, 43), ("yield", 43, 45), ("step", 45, 60),
             ("pack", 46, 48), ("launch", 48, 49), ("sync", 49, 59)]
    ctx = with_requests(make_ctx(PROGRAMS, spans=spans), [("submit", 36, 44, "r1"), ("deliver", 44, 49.5, "r2")])
    got = parts(ctx)
    assert got["yield"] == pytest.approx(3.0)        # yield 2 + the hand-off [45, 46)
    assert got["submit"] == pytest.approx(1.0)       # [43, 44) of the yield span
    assert got["deliver"] == pytest.approx(2.0)      # [44, 45) of it and [45, 46) of step
    assert got["unnamed"] == pytest.approx(0.0)
    shares = hs.reduce(ctx)                           # the six groups are what they were
    assert shares["readback"] == pytest.approx(2.0) and shares["emit"] == pytest.approx(1.0)
    assert shares["dispatch"] == pytest.approx(4.0)  # pack 2, launch 1, launch lag 1


def test_the_three_add_up_to_idle_yield_share():
    spans = [("yield", 38, 43), ("idle", 43, 44.5), ("admit", 44.5, 45), ("step", 45, 60), ("pack", 47, 49)]
    quads = [("submit", 38.5, 41.25, "a"), ("deliver", 41, 41.75, "b"), ("submit", 45.2, 46.1, "c"),
             ("deliver", 30, 39, "d"), ("deliver", 49.5, 70, "e")]
    ctx = with_requests(make_ctx(PROGRAMS, spans=spans), quads)
    for sfx in (".tput", ".lat"):
        named = [reader(f"{n}{sfx}")(ctx) for n in NEW]
        assert all(v >= 0.0 for v in named)
        assert sum(named) == pytest.approx(reader(f"idle_yield_share{sfx}")(ctx), abs=1e-9)
    assert parts(ctx)["unnamed"] > 0.0


def test_a_program_without_request_spans_names_nothing():
    """The parent of PR 35: ``host_spans`` and no ``request_spans``. Zeros
    there mean "not named", and the whole ``yield`` group is unnamed."""
    ctx = with_requests(make_ctx(PROGRAMS, spans=[("yield", 38, 52)], admit=[0.01]), None)
    for sfx in (".tput", ".lat"):
        assert reader(f"idle_submit_share{sfx}")(ctx) == 0.0
        assert reader(f"idle_deliver_share{sfx}")(ctx) == 0.0
        assert reader(f"idle_yield_unnamed_share{sfx}")(ctx) == reader(f"idle_yield_share{sfx}")(ctx) == pytest.approx(10.0)
    for sfx in (".closed", ".open"):
        assert reader(f"submit_p50_ms{sfx}")(ctx) == 0.0


def test_spans_outside_the_traced_window_or_every_gap_read_zero():
    ctx = with_requests(make_ctx(PROGRAMS, spans=[("yield", 38, 52)]),
                        [("submit", -20, -5, "early"), ("submit", 20, 30, "busy"), ("deliver", 120, 130, "late")])
    got = parts(ctx)
    assert got["submit"] == got["deliver"] == 0.0 and got["unnamed"] == pytest.approx(10.0)


def test_no_trace_or_no_spans_at_all_reads_none():
    ctx = with_requests(make_ctx(PROGRAMS, spans=[("yield", 38, 52)]), [("submit", 42, 46, "r")])
    ctx.trace = None
    assert reader("idle_submit_share.tput")(ctx) is None
    assert reader("submit_p50_ms.closed")(ctx) == pytest.approx(4.0)   # needs no trace
    bare = make_ctx(PROGRAMS)                          # a program older than PR 24
    assert reader("idle_yield_unnamed_share.lat")(bare) is None
    assert reader("submit_p50_ms.open")(bare) is None
    assert reader("mixed_step_gap_ms.tput")(bare) is None


def test_submit_time_is_summed_by_request_and_the_median_taken():
    quads = [("submit", 1, 3, "a"), ("submit", 5, 6, "a"),      # cut at an await: 3 ms
             ("submit", 10, 11, "b"), ("deliver", 11, 40, "b"),  # 1 ms
             ("submit", 20, 38, "c")]                             # 18 ms
    ctx = with_requests(make_ctx(PROGRAMS, spans=[]), quads)
    for sfx in (".closed", ".open"):
        assert reader(f"submit_p50_ms{sfx}")(ctx) == pytest.approx(3.0)
    assert reader("submit_p50_ms.closed")(with_requests(make_ctx(PROGRAMS, spans=[]), [])) == 0.0


# -- the host's gap before a step ------------------------------------------------
def step(phase, spans, **fields):
    flat = tuple(v for n, a, b in spans for v in (n, at(a), at(b)))
    return (1000.05, types.SimpleNamespace(phase=phase, host_spans=flat, **fields))


def gap_ctx(steps):
    return types.SimpleNamespace(steps=steps, trace=None)


def test_a_gap_runs_from_the_results_to_the_next_launch_and_goes_by_the_carrying_step():
    steps = [
        step("decode", [("fetch", 0, 10), ("emit", 10, 11), ("step", 11, 13), ("pack", 11, 12), ("launch", 12, 13)]),
        # a chunk meets the batch: the horizon's results, then 22 ms of host, then the launch
        step("mixed", [("fetch", 13, 20), ("emit", 20, 21), ("yield", 21, 35), ("book", 35, 36),
                       ("step", 36, 60), ("pack", 36, 40), ("upload", 40, 41), ("launch", 41, 42), ("sync", 42, 60)]),
        step("prefill", [("yield", 60, 70), ("step", 70, 90), ("launch", 71, 72), ("sync", 72, 90)]),
        step("decode", [("step", 90, 93), ("launch", 92, 93)]),
    ]
    ctx = gap_ctx(steps)
    assert _step_gaps.gaps_ms(ctx) == {"chunk": [pytest.approx(22.0), pytest.approx(12.0)],
                                       "decode": [pytest.approx(3.0), pytest.approx(3.0)]}
    for sfx in (".tput", ".tpot"):
        assert reader(f"mixed_step_gap_ms{sfx}")(ctx) == pytest.approx(17.0)
        assert reader(f"horizon_gap_ms{sfx}")(ctx) == pytest.approx(3.0)


def test_a_launch_with_no_wait_since_the_last_one_is_left_out():
    steps = [step("decode", [("launch", 1, 2),                       # the window's first: no results yet
                             ("fetch", 2, 10), ("launch", 11, 12),  # 2 ms
                             ("launch", 13, 14),                    # topped up again in the same tick
                             ("sync", 14, 20), ("launch", 24, 25)])]  # 5 ms
    assert _step_gaps.gaps_ms(gap_ctx(steps))["decode"] == [pytest.approx(2.0), pytest.approx(5.0)]
    assert reader("mixed_step_gap_ms.tput")(gap_ctx(steps)) is None   # no chunk-carrying step here


# -- the two counters --------------------------------------------------------------
def test_placements_a_step_and_the_run_share():
    steps = [step("mixed", [], h2d_placements=4, mla_chunks_whole=600, mla_chunks_run=588),
             step("decode", [], h2d_placements=0, mla_chunks_whole=400, mla_chunks_run=391),
             step("prefill", [], h2d_placements=2, mla_chunks_whole=None, mla_chunks_run=None)]
    ctx = gap_ctx(steps)
    for sfx in (".tput", ".tpot"):
        assert reader(f"h2d_placements_per_step{sfx}")(ctx) == pytest.approx(2.0)
    assert reader("mla_run_chunk_share.tput")(ctx) == pytest.approx(97.9)
    assert reader("mla_run_chunk_share.tput")(gap_ctx([step("decode", [], h2d_placements=1)])) is None


# -- the manifest --------------------------------------------------------------------
def test_the_manifest_lists_the_thirty_by_the_metric_each_moves():
    manifest = contract.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in manifest["per_layer"]}
    closed = next(e for e in manifest["end_to_end"] if e["name"] == "output_tokens_per_s")["workloads"]
    opened = next(e for e in manifest["end_to_end"] if e["name"] == "tpot_p95_ms")["workloads"]
    assert len(closed) == 5 and opened == ["internlm2-chat-steady"]
    with open(os.path.join(ROOT, "benchmarks", "metrics", "host_spans.per_layer.json")) as f:
        pr24 = json.load(f)
    new = [f"{n}{sfx}" for n in NEW for sfx in (".tput", ".lat")] + [
        "submit_p50_ms.closed", "submit_p50_ms.open", "mixed_step_gap_ms.tput", "mixed_step_gap_ms.tpot",
        "horizon_gap_ms.tput", "horizon_gap_ms.tpot", "h2d_placements_per_step.tput", "h2d_placements_per_step.tpot"]
    assert len(pr24) == 15 and len(new) == 14
    for name in [m["name"] for m in pr24] + new:
        m = listed[name]
        tput = name.rsplit(".", 1)[1] in ("tput", "closed")
        assert m["moves"] == ("output_tokens_per_s" if tput else "tpot_p95_ms"), name
        assert m["workloads"] == (closed if tput else opened), name
        assert reader(name)
    for m in pr24:    # the entries as PR 24 kept them, but for their cells
        assert {k: v for k, v in listed[m["name"]].items() if k != "workloads"} == {k: v for k, v in m.items() if k != "workloads"}
    assert listed["mla_run_chunk_share.tput"]["workloads"] == ["axk1-docqa-repeat"]
    assert [m["name"] for m in manifest["per_layer"]][-30:] == [m["name"] for m in pr24] + new[:-2] + [
        "mla_run_chunk_share.tput"] + new[-2:]


def test_a_parent_like_line_passes_the_contract_in_every_cell():
    """Every listed per-layer reader PR 35 brings gives a finite number on a
    context without ``request_spans``, so the parent's traced line holds."""
    spans = [("fetch", 30, 41), ("yield", 41, 45), ("step", 45, 60), ("launch", 46, 47), ("sync", 47, 60)]
    ctx = make_ctx(PROGRAMS, spans=spans, admit=[0.01])
    s = ctx.steps[0][1]
    s.h2d_placements, s.mla_chunks_whole, s.mla_chunks_run = 1, 10, 9
    ctx.steps.append((1000.06, types.SimpleNamespace(
        phase="mixed", host_spans=("sync", at(61), at(70), "launch", at(75), at(76)), admit_wait_s=(),
        h2d_placements=1, mla_chunks_whole=10, mla_chunks_run=10)))
    manifest = contract.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    for m in manifest["per_layer"][-30:]:
        value = reader(m["name"])(ctx)
        assert value is not None and math.isfinite(value), m["name"]
