"""The launch ledger joined to the device's program executions: one clock for
a profile's host and device, and each idle gap between two executions cut
into readback, the host's turn and launch lag. What the ``gap_*_share``,
``clock_offset_width_ms``, ``launch_lag_p50_ms``, ``result_lag_p50_ms``,
``device_wait_before_*_ms``, ``chunk_step_ms_per_token`` and
``programs_compiled_in_window`` readers share, done once a run and kept on
``ctx``.

The program (``dynamo_tpu/engine/telemetry.py`` ``launch``,
``record_arrival``) names every jitted call its serving loop makes and every
arrival of a call's results, and hands both to the benchmark's ``stats_hook``
on the next ``StepStats``: ``launches``, flat, seven values a launch (``seq,
program, key, t0_ns, t1_ns, compiled, after``: ``seq`` one counter in launch
order, ``program`` what the device trace shows behind ``jit_``, ``key`` the
chunk's bucket or the steps a decode program advances a row, the call's two
stamps on ``time.monotonic_ns()``, whether this call compiled a program or
loaded one from the compile cache (JAX recorded a backend compile on the
calling thread across it, the event ``run.py`` counts), and the ``seq`` of
the newest launch whose results the loop had
taken when it made this one, -1 for none), and ``arrivals``, two values an
arrival (``seq, t_ns``, stamped on the thread that learns it as its blocking
conversion returns).

The join. Launches and arrivals are moved onto the trace's clock by the
benchmark's marker, as ``_host_spans.py`` moves the spans; that clock is good
to about a millisecond (the estimated offsets read -1.07 to +0.82 ms over 27
profiles, PERF.md section 6, PR 51) and the join allows it three
(``SLACK_NS``: at one, the profile at -1.07 left nine executions whose calls
seemed to begin after they started without a launch; at three the 26 others
join as they did, to the last digit). The device runs one program at a time and
in launch order, so device 0's executions inside the sub-window are a run of
the launch sequence: each in turn takes the next launch if its ``program`` is
the execution's name and its call began before the execution started. A
launch whose results (or a later launch's) were on the host before the
execution ENDED ran earlier and is passed over: results cannot land before
their program ends (the device may run two launches behind the host where
chunks are chained, so a launch's call can lie far before its execution).
An execution that finds no partner is COUNTED (its gap goes to ``unjoined``), not dropped.

The offset. With ``s_k``, ``e_k`` the start and end of execution k on the
device's clock and ``a_k``, ``r_k`` the start of its launch's call and the
arrival of its results on the marker's clock, the device's clock is the
marker's plus ``d``, and for every k::

    e_k - r_k  <=  d  <=  s_k - a_k

(its results cannot be on the host before the program ended; the program
cannot start before the call that launched it began). ``d`` lies between the
largest left side and the smallest right side. The ESTIMATE is the interval's
UPPER end: the fastest launch of the window defines zero launch lag. A launch
onto an idle device places one small packed buffer and enqueues, on one
thread; a readback crosses the device-to-host copy and two threads: the first
is the steadier latency. The interval's WIDTH is the fastest launch's own lag
plus the fastest readback's: what of the shortest readback could be launch lag
instead (1.8-2.4 ms on a v5e, PERF.md section 6, PR 51: the fastest of a
window's arrivals lands that long after its program ended, less the few
tenths a launch takes). So **readback** below holds the fastest launch's own
lag once a gap, the same in every profile. An EMPTY interval (a negative width) means a
wrong pairing or a wrong stamp; it is reported as it is, never clamped. An
execution cut by the sub-window's edge gives no inequality on the side that
is cut.

The split. Each idle gap of device 0 between two executions, while a request
was in flight (``breakdown.idle_gaps``' ``between_steps``), that ends at the
start of a joined execution k is cut at two host instants on the estimated
clock: the arrival of the results of launch k's ``after`` (what the loop had
to have before it made launch k; the record says which, the reader does not
guess; with none, or one that landed before the gap began, the cut is the
gap's start) and ``a_k``. Gap start to arrival is **readback**, arrival to
``a_k`` the **host's turn**, ``a_k`` to the gap's end **launch lag**: the
three tile the gap. A gap that ends at an execution without a launch is
**unjoined**; the window's last gap, which ends at no execution, is cut by
the launch after the last joined one. Each part is reported as % of the traced
window, and the four add up to ``between_steps`` over the window.

A program that predates the two fields has nothing to join: ``reduce``
returns ``None`` and every reader returns 0.0, "not recorded", a finite
number because ``contract.check_line`` refuses a line that lacks a listed
metric and the driver runs the parent under these files.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmarks import trace_reduce

VALUES = 7                       # of a launch record
SEQ, PROGRAM, KEY, T0, T1, COMPILED, AFTER = range(VALUES)
SLACK_NS = 3_000_000             # what the marker's clock may be off by
CHUNK_PROGRAMS = ("mixed_step", "prefill")
HORIZON_PROGRAMS = ("decode_multi", "decode", "spec_multi")
PARTS = ("readback", "host_turn", "launch_lag", "unjoined")

Launch = Tuple[Any, ...]
Interval = Tuple[int, int]


def records(steps) -> Optional[Tuple[List[Launch], Dict[int, int]]]:
    """The launches of ``steps`` (``(t, StepStats)`` pairs) in launch order and
    the first arrival of each ``seq``, on the program's clock; ``None`` when
    no step has the fields."""
    have = [s for _, s in steps if hasattr(s, "launches")]
    if not have:
        return None
    launches = [rec for s in have
                for rec in zip(*(s.launches[i::VALUES] for i in range(VALUES)))]
    arrivals: Dict[int, int] = {}
    for s in have:
        for seq, t in zip(s.arrivals[0::2], s.arrivals[1::2]):
            arrivals.setdefault(seq, t)
    return sorted(launches), arrivals


def program_of(module: str) -> str:
    """``jit_mixed_step(8291593670660239595)`` -> ``mixed_step``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def join(modules, launches: List[Launch], arrivals: Dict[int, int],
         shift: int) -> List[Optional[int]]:
    """For each execution (``name, start_ns, duration_ns``, in start order)
    the index of its launch in ``launches``, or ``None``."""
    call = [rec[T0] + shift for rec in launches]
    landed = [arrivals[rec[SEQ]] + shift if rec[SEQ] in arrivals else None
              for rec in launches]
    # by when a launch had surely run: its own results or a later launch's
    ran_by, soonest = [0] * len(launches), float("inf")
    for p in range(len(launches) - 1, -1, -1):
        if landed[p] is not None:
            soonest = min(soonest, landed[p])
        ran_by[p] = soonest
    partner: List[Optional[int]] = []
    p = 0
    for name, s, d in modules:
        # results on the host before this execution ENDED are an earlier one's
        while p < len(launches) and ran_by[p] + SLACK_NS < s + d:
            p += 1
        if (p < len(launches) and launches[p][PROGRAM] == program_of(name)
                and call[p] <= s + SLACK_NS):
            partner.append(p)
            p += 1
        else:
            partner.append(None)
    return partner


def offset_interval(pairs, lo: int, hi: int) -> Tuple[Optional[int], Optional[int]]:
    """``(largest e_k - r_k, smallest s_k - a_k)`` over ``pairs`` of ``(s, e,
    a, r)``, ``r`` ``None`` without an arrival; a side with no inequality is
    ``None``. Executions cut by ``lo`` / ``hi`` give none on the cut side."""
    upper = [s - a for s, e, a, r in pairs if s > lo]
    lower = [e - r for s, e, a, r in pairs if r is not None and e < hi]
    return (max(lower) if lower else None, min(upper) if upper else None)


def reduce(ctx) -> Optional[Dict[str, Any]]:
    """The join, the offset and the split of one traced run; ``None`` without
    a trace or from a program without the fields. Kept on ``ctx``."""
    if hasattr(ctx, "_launch_join"):
        return ctx._launch_join
    ctx._launch_join = None
    red = ctx.trace
    recs = records(ctx.steps_all)
    if red is None or recs is None:
        return None
    launches, arrivals = recs
    lo, hi = red.lo, red.hi
    shift = lo - int(ctx.trace_host[0] * 1e9)
    modules = sorted(red.modules, key=lambda m: m[1])
    partner = join(modules, launches, arrivals, shift)

    def landed(seq: int) -> Optional[int]:
        return arrivals[seq] + shift if seq in arrivals else None

    joined = [(i, p) for i, p in enumerate(partner) if p is not None]
    pairs = [(modules[i][1], modules[i][1] + modules[i][2],
              launches[p][T0] + shift, landed(launches[p][SEQ])) for i, p in joined]
    d_lo, d_hi = offset_interval(pairs, lo, hi)
    mid = (lo + hi) // 2
    halves = [offset_interval([q for q in pairs if (q[0] < mid) == first], lo, hi)[1]
              for first in (True, False)]
    out: Dict[str, Any] = {
        "executions": len(modules), "joined": len(joined), "launches": len(launches),
        "offset_ns": d_hi, "offset_lower_ns": d_lo, "offset_halves_ns": halves,
        "width_ms": (d_hi - d_lo) / 1e6 if d_hi is not None and d_lo is not None else None,
    }
    d = d_hi or 0

    # the split of the between-steps idle
    busy = trace_reduce.merge((s, s + n) for _, s, n in red.ops)
    programs = trace_reduce.merge((s, s + n) for _, s, n in modules)
    between = trace_reduce.gaps(programs, lo, hi)
    flight = trace_reduce.merge(trace_reduce.clip(
        ((int(r["t_ref"] * 1e9) + shift, int(r["t_last_or_end"] * 1e9) + shift)
         for r in ctx.requests_all), lo, hi))
    counted = trace_reduce.intersect(
        trace_reduce.intersect(trace_reduce.gaps(busy, lo, hi), between), flight)
    starts_at = {m[1]: i for i, m in enumerate(modules)}
    window = hi - lo

    def split(d: Optional[int]) -> Dict[str, float]:
        """The four parts, % of the window, with the device's clock the
        marker's plus ``d``."""
        parts: Dict[str, List[Interval]] = {name: [] for name in PARTS}
        for g0, g1 in between:
            i = starts_at.get(g1)
            p = partner[i] if i is not None else None
            if i is None and g1 == hi and joined and joined[-1][0] == len(modules) - 1:
                # the window's last gap: the launch after the last joined one
                p = joined[-1][1] + 1 if joined[-1][1] + 1 < len(launches) else None
            if p is None or d is None:
                parts["unjoined"].append((g0, g1))
                continue
            results = landed(launches[p][AFTER])
            cut_r = min(max(results + d, g0), g1) if results is not None else g0
            cut_a = min(max(launches[p][T0] + shift + d, cut_r), g1)
            parts["readback"].append((g0, cut_r))
            parts["host_turn"].append((cut_r, cut_a))
            parts["launch_lag"].append((cut_a, g1))
        return {
            name: 100.0 * trace_reduce.total_ns(trace_reduce.intersect(
                [(a, b) for a, b in cuts if b > a], counted)) / window
            for name, cuts in parts.items()
        }

    out["shares"] = split(d_hi)
    # the same gaps cut with the offset at the interval's OTHER end: how far
    # readback and launch lag could trade (the bracket of each; not a metric)
    out["shares_at_lower_end"] = split(d_lo) if d_hi is not None else out["shares"]
    out["between_steps_share"] = 100.0 * trace_reduce.total_ns(counted) / window

    # per execution: the device's wait before it, the launch's lag, the
    # results' lag, a chunk's device time and tokens
    waits: Dict[str, List[float]] = {}
    lags, result_lags = [], []
    chunk_ns = chunk_tokens = 0
    for i, p in joined:
        _, s, n = modules[i]
        program, a = launches[p][PROGRAM], launches[p][T0] + shift + d
        if i > 0:
            ended = modules[i - 1][1] + modules[i - 1][2]
            waits.setdefault(program, []).append((s - ended) / 1e6)
            if d_hi is not None and a >= ended:  # launched onto an idle device
                lags.append((s - a) / 1e6)
        results = landed(launches[p][SEQ])
        if d_hi is not None and results is not None and s + n < hi:
            result_lags.append((results + d - (s + n)) / 1e6)
        if program in CHUNK_PROGRAMS and s > lo and s + n < hi:
            chunk_ns += n
            chunk_tokens += int(launches[p][KEY])
    out["waits_ms"], out["launch_lags_ms"], out["result_lags_ms"] = waits, lags, result_lags
    out["chunk_ms_per_token"] = chunk_ns / 1e6 / chunk_tokens if chunk_tokens else None
    print("# launches: " + ", ".join(
        f"{k} {out[k]}" for k in ("executions", "joined", "launches", "offset_ns",
                                  "offset_lower_ns", "offset_halves_ns", "width_ms",
                                  "shares", "shares_at_lower_end",
                                  "between_steps_share")), flush=True)
    ctx._launch_join = out
    return out


def _read(ctx, pick) -> Optional[float]:
    """``pick`` of the reduced run: 0.0 from a program without the ledger,
    ``None`` without a trace."""
    if not any(hasattr(s, "launches") for _, s in ctx.steps_all):
        return 0.0
    out = reduce(ctx)
    return None if out is None else pick(out)


def share(ctx, part: str) -> Optional[float]:
    return _read(ctx, lambda out: out["shares"][part])


def width_ms(ctx) -> Optional[float]:
    return _read(ctx, lambda out: out["width_ms"])


def median_ms(ctx, field: str) -> Optional[float]:
    """Median of ``launch_lags_ms`` / ``result_lags_ms``; 0.0 where the window
    holds none (no launch found the device idle: none lagged)."""
    return _read(ctx, lambda out: statistics.median(out[field]) if out[field] else 0.0)


def wait_ms(ctx, programs) -> Optional[float]:
    """Median idle of device 0 directly before the joined executions of
    ``programs``; ``None`` where the sub-window holds none."""
    def pick(out):
        waits = [w for prog in programs for w in out["waits_ms"].get(prog, ())]
        return statistics.median(waits) if waits else None
    return _read(ctx, pick)


def chunk_ms_per_token(ctx) -> Optional[float]:
    return _read(ctx, lambda out: out["chunk_ms_per_token"])


def compiled_in_window(ctx) -> float:
    """Launches that compiled or loaded a program, of those the window's
    ``StepStats`` carry (no trace needed)."""
    recs = records(ctx.steps)
    return 0.0 if recs is None else float(sum(1 for rec in recs[0] if rec[COMPILED]))
