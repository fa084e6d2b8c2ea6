"""The launch-ledger readers (``benchmarks/metrics/_launches.py``) on a
hand-built run: executions on the device's clock, launches and arrivals on
the host's, a PLANTED offset between the two that the marker does not know
of, known lags on both sides of it."""
import json
import os
import types

import pytest

from benchmarks import contract, trace_reduce as tr
from benchmarks.metrics import _launches as la
from benchmarks.tests.test_host_spans import HOST0, LAYOUT, MS, TRACE0, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1_000
D = 350 * US        # the device's clock runs this far ahead of the marker's
NAMES = [f"{n}{sfx}" for n, pair in [
    ("gap_readback_share", (".tput", ".lat")), ("gap_host_turn_share", (".tput", ".lat")),
    ("gap_launch_lag_share", (".tput", ".lat")), ("gap_unjoined_share", (".tput", ".lat")),
    ("clock_offset_width_ms", (".tput", ".lat")), ("launch_lag_p50_ms", (".tput", ".tpot")),
    ("result_lag_p50_ms", (".tput", ".tpot")), ("device_wait_before_mixed_ms", (".tput", ".tpot")),
    ("device_wait_before_horizon_ms", (".tput", ".tpot")), ("chunk_step_ms_per_token", (".tput", ".tpot")),
    ("programs_compiled_in_window", (".tput", ".tpot"))] for sfx in pair]


def host(ns_in_window):
    """An instant of the window on the device's clock -> the program's clock."""
    return int(HOST0 * 1e9) + ns_in_window - D


class Run:
    """Executions ``(program, key, start_ms, end_ms)`` in order; each gets a
    launch whose call began ``lag_us`` before it started and, where
    ``read_us`` is given, results that landed that long after it ended."""

    def __init__(self, window_ms=100):
        self.window_ms, self.mods, self.launches, self.arrivals = window_ms, [], [], []
        self.seq = 100

    def run(self, program, key, start_ms, end_ms, lag_us=200, read_us=400, after=None,
            compiled=False, recorded=True):
        s, e = int(start_ms * MS), int(end_ms * MS)
        self.mods.append([f"jit_{program}({len(self.mods)})", TRACE0 + s, e - s])
        if recorded:
            a = host(s) - lag_us * US
            self.launches += [self.seq, program, key, a, a + 50 * US, compiled,
                              self.seq - 1 if after is None else after]
            if read_us is not None:
                self.arrivals += [self.seq, host(e) + read_us * US]
            self.seq += 1
        return self

    def ctx(self, fields=True):
        trace = {"planes": [
            {"name": "/host:CPU", "lines": [{"name": "python", "events": [
                ["benchmark_window", TRACE0, self.window_ms * MS]]}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": self.mods},
                {"name": "XLA Ops", "events": [[f"fusion.{k}", s, n] for k, (_, s, n) in enumerate(self.mods)]}]},
        ]}
        ctx = types.SimpleNamespace()
        ctx.trace = tr.Reduced(trace, LAYOUT)
        ctx.trace_host = (HOST0, HOST0 + self.window_ms / 1e3)
        step = types.SimpleNamespace(phase="decode")
        if fields:
            step.launches, step.arrivals = tuple(self.launches), tuple(self.arrivals)
        ctx.steps = ctx.steps_all = [(HOST0 + 0.05, step)]
        ctx.requests_all = [{"t_ref": HOST0 - 1.0, "t_last_or_end": HOST0 + 1.0}]
        return ctx


def steady():
    """Horizons of 20 ms, 5 ms apart, launched after the one before was read:
    a gap is 0.4 ms + D of readback, then the host's turn, then the lag."""
    run = Run()
    run.run("decode_multi", 8, -15, 0)                  # ended before the window
    for k, (lag, read) in enumerate([(900, 400), (200, 700), (500, 300), (650, 450)]):
        run.run("decode_multi", 8, 5 + 25 * k, 25 + 25 * k, lag_us=lag, read_us=read)
    return run


def test_the_interval_holds_the_planted_offset_and_the_estimate_is_its_upper_end():
    out = la.reduce(steady().ctx())
    assert (out["executions"], out["joined"]) == (4, 4)
    # the fastest launch lagged 200 us, the fastest readback 300 us
    assert out["offset_lower_ns"] == D - 300 * US and out["offset_ns"] == D + 200 * US
    assert out["offset_lower_ns"] <= D <= out["offset_ns"]
    assert out["width_ms"] == pytest.approx(0.5)
    assert out["offset_halves_ns"] == [D + 200 * US, D + 500 * US]
    for sfx in (".tput", ".lat"):
        assert reader(f"clock_offset_width_ms{sfx}")(steady().ctx()) == pytest.approx(0.5)


def test_the_three_parts_tile_every_joined_gap():
    """Each of the four gaps (5 ms): the results of the launch before landed
    ``read`` after the gap began, the call began ``lag`` before it ended. On
    the estimated clock the fastest launch's lag is zero: 200 us a gap move
    from launch lag to readback, and the host's turn keeps its length."""
    out = la.reduce(steady().ctx())
    shares = out["shares"]
    readback_us = (400 + 400 + 700 + 300) + 4 * 200
    lag_us = (900 + 200 + 500 + 650) - 4 * 200
    assert shares["unjoined"] == 0.0
    assert shares["readback"] == pytest.approx(readback_us / 1e3)
    assert shares["launch_lag"] == pytest.approx(lag_us / 1e3)
    assert shares["host_turn"] == pytest.approx(20.0 - (readback_us + lag_us) / 1e3)
    assert sum(shares.values()) == pytest.approx(out["between_steps_share"]) == pytest.approx(20.0)
    for part in ("readback", "host_turn", "launch_lag", "unjoined"):
        assert reader(f"gap_{part}_share.tput")(steady().ctx()) == pytest.approx(shares[part])


@pytest.mark.parametrize("planted_us", [-2_500, -1_073, 2_500])
def test_the_join_holds_where_the_markers_clock_is_off_by_more_than_a_millisecond(monkeypatch, planted_us):
    """A profile read -1.07 ms (the chat cell, PR 51): a call then seems to
    begin after its execution started, and every execution still joins."""
    import sys
    monkeypatch.setattr(sys.modules[__name__], "D", planted_us * US)
    out = la.reduce(steady().ctx())
    assert (out["executions"], out["joined"]) == (4, 4)
    assert out["offset_lower_ns"] <= planted_us * US <= out["offset_ns"]
    assert out["shares"]["unjoined"] == 0.0


@pytest.mark.parametrize("part, moves_us", [
    ("readback", -4 * 500), ("launch_lag", +4 * 500), ("host_turn", 0), ("unjoined", 0)])
def test_the_other_end_of_the_interval_brackets_readback_and_launch_lag(part, moves_us):
    """Cut with the offset at the interval's LOWER end (the fastest readback
    defines zero) every gap hands its width, 0.5 ms, from readback to launch
    lag: the bracket of each, printed beside the shares and no metric."""
    out = la.reduce(steady().ctx())
    upper, lower = out["shares"], out["shares_at_lower_end"]
    assert lower[part] - upper[part] == pytest.approx(moves_us / 1e3)
    assert sum(lower.values()) == pytest.approx(sum(upper.values()))


def test_the_windows_last_gap_is_cut_by_the_launch_after_the_last_joined_one():
    run = Run()
    run.run("decode_multi", 8, 10, 30, lag_us=0).run("decode_multi", 8, 40, 90, lag_us=3_000)
    assert la.reduce(run.ctx())["shares"]["unjoined"] == pytest.approx(10.0)  # [90, 100): no launch after
    run.run("decode_multi", 8, 104, 124, lag_us=6_000)    # starts past the window:
    run.mods.pop()                                        # the trace lacks it, the ledger has it
    shares = la.reduce(run.ctx())["shares"]
    # [90, 100): the results of the second landed 0.4 ms in, the call began at 98
    assert shares["unjoined"] == 0.0
    assert shares["readback"] == pytest.approx(0.4 + 0.4)
    assert shares["launch_lag"] == pytest.approx(3.0 + 2.0)
    assert sum(shares.values()) == pytest.approx(30.0)


def test_an_execution_without_a_launch_is_counted_as_unjoined():
    run = Run()
    run.run("decode_multi", 8, 10, 30).run("convert_element_type", 0, 40, 41, recorded=False)
    run.run("decode_multi", 8, 50, 70, after=100).run("mixed_step", 256, 80, 90)
    out = la.reduce(run.ctx())
    assert (out["executions"], out["joined"]) == (4, 3)
    # [30, 40) ends at the execution nobody launched; [90, 100) at none
    assert out["shares"]["unjoined"] == pytest.approx(20.0)
    assert sum(out["shares"].values()) == pytest.approx(out["between_steps_share"]) == pytest.approx(49.0)


def test_a_crossed_pairing_gives_a_negative_width():
    """Two short executions one after the other, inside the marker's slack,
    with their arrivals crossed: the second's results are said to have landed
    0.2 ms BEFORE it ended. The interval is empty and is reported so, not
    clamped; the straight pairing reads the planted lags."""
    def pair():
        return Run().run("decode", 1, 10.0, 10.2, lag_us=100, read_us=100).run(
            "decode", 1, 10.3, 10.5, lag_us=100, read_us=100)

    assert reader("clock_offset_width_ms.tput")(pair().ctx()) == pytest.approx(0.2)
    crossed = pair()
    crossed.arrivals[1], crossed.arrivals[3] = crossed.arrivals[3], crossed.arrivals[1]
    out = la.reduce(crossed.ctx())
    assert out["joined"] == 2
    assert (out["offset_lower_ns"], out["offset_ns"]) == (D + 200 * US, D + 100 * US)
    width = reader("clock_offset_width_ms.lat")(crossed.ctx())
    assert width == pytest.approx(-0.1) and width < 0


def test_a_launch_on_a_device_carry_has_no_readback():
    """Chained mixed links: link N + 1 came ``after`` the results of N - 1,
    which landed before the gap began; what the device waited for is the
    host's turn and the lag."""
    run = Run()
    run.run("mixed_step", 256, 10, 30, lag_us=0).run("mixed_step", 256, 31, 51, lag_us=15_000, after=99)
    run.run("mixed_step", 128, 55, 65, lag_us=1_000, after=100)
    out = la.reduce(run.ctx())
    # [0, 10): the first call began as it started: the host's turn. [30, 31):
    # the call began 15 ms before: all of it lag. [51, 55): the results of
    # 100 landed long before, the call began 1 ms before the end
    assert out["shares"]["readback"] == 0.0
    assert out["shares"]["launch_lag"] == pytest.approx(1.0 + 1.0)
    assert out["shares"]["host_turn"] == pytest.approx(10.0 + 3.0)
    # only the first and the third found the device idle; the chunk's tokens weigh the mean
    assert reader("launch_lag_p50_ms.tput")(run.ctx()) == pytest.approx(1.0)
    assert reader("device_wait_before_mixed_ms.tpot")(run.ctx()) == pytest.approx(2.5)
    assert reader("device_wait_before_horizon_ms.tput")(run.ctx()) is None
    assert reader("chunk_step_ms_per_token.tput")(run.ctx()) == pytest.approx(50 / 640)
    assert reader("result_lag_p50_ms.tpot")(run.ctx()) == pytest.approx(0.4)


def test_launches_that_ran_before_the_window_are_passed_over():
    """A lone prompt's chunks before the window (no arrival but the last's)
    and horizons read long ago do not take the window's executions."""
    run = Run()
    for k in range(3):
        run.run("prefill", 512, -90 + 10 * k, -81 + 10 * k, read_us=None if k < 2 else 400)
    run.run("decode_multi", 8, -50, -30)
    before = len(run.mods)
    run.run("prefill", 512, 10, 19, read_us=None).run("prefill", 128, 20, 24).run("decode_multi", 8, 30, 50)
    run.mods = run.mods[before:]
    out = la.reduce(run.ctx())
    assert (out["executions"], out["joined"], out["launches"]) == (3, 3, 7)
    assert reader("chunk_step_ms_per_token.tpot")(run.ctx()) == pytest.approx(13 / 640)


def test_compiles_are_counted_from_the_windows_steps_without_a_trace():
    run = steady().run("mixed_step", 256, 110, 120, compiled=True)
    ctx = run.ctx()
    ctx.trace = None
    for sfx in (".tput", ".tpot"):
        assert reader(f"programs_compiled_in_window{sfx}")(ctx) == 1.0
    assert reader("gap_readback_share.tput")(ctx) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_fields_reads_zero_from_every_reader(name):
    got = reader(name)(steady().ctx(fields=False))
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_with_the_fields_reads_a_finite_number(name):
    run = Run().run("decode_multi", 8, 5, 25).run("mixed_step", 256, 30, 40).run("decode_multi", 8, 45, 65)
    value = reader(name)(run.ctx())
    assert isinstance(value, float) and value == value


def test_the_entries_were_appended_in_one_run_and_fit_the_contract():
    manifest = contract.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    first = [m["name"] for m in manifest["per_layer"]].index(NAMES[0])
    assert first >= 78    # behind everything PR 50 left (a later PR appends behind these)
    mine = manifest["per_layer"][first:first + len(NAMES)]
    assert [m["name"] for m in mine] == NAMES
    cells = [c["name"] for c in manifest["workloads"]]
    reports = {e["name"]: e.get("workloads", cells) for e in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"][:first]}
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        tput = m["name"].endswith(".tput")
        assert m["moves"] == ("output_tokens_per_s" if tput else "tpot_p95_ms")
        assert set(m["workloads"]) <= set(reports[m["moves"]]) and m["layer"] in layers
        assert m["better"] == "lower" and callable(reader(m["name"]))
        assert m["source"] == ("program_counter" if m["name"].startswith("programs_") else "device_trace")
        if not tput:
            assert m["workloads"] == ["internlm2-chat-steady"]
    # a chunk-carrying execution in every sub-window: where prefill_chunk_ms is listed
    assert listed["chunk_step_ms_per_token.tput"]["workloads"] == listed["prefill_chunk_ms.tput"]["workloads"]
    assert listed["device_wait_before_mixed_ms.tput"]["workloads"] == [
        c for c in listed["prefill_chunk_ms.tput"]["workloads"] if c != "glm52-longdoc-sessions"]
    assert listed["gap_readback_share.tput"]["workloads"] == reports["output_tokens_per_s"]
    # the same entries, by the rehearsal's cells, kept beside its manifest
    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "launches.entries.json")) as f:
        kept = json.load(f)
    assert [{k: v for k, v in m.items() if k != "workloads"} for m in kept] == [
        {k: v for k, v in m.items() if k != "workloads"} for m in mine]
    rehearsal = contract.load_manifest(os.path.join(ROOT, "benchmarks", "rehearsal", "BENCHMARK.json"))
    reports = {e["name"]: e.get("workloads") for e in rehearsal["end_to_end"]}
    assert all(m["workloads"] == reports[m["moves"]] for m in kept)
    assert not {m["name"] for m in kept} & {m["name"] for m in rehearsal["per_layer"]}
