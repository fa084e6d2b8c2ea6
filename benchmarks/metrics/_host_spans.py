"""What the host was doing while the device sat idle between two program
executions, and how long a request waited to be admitted: the arithmetic the
``idle_*_share`` and ``admit_wait_*`` readers share, done once per run and
kept on ``ctx``.

The program (``dynamo_tpu/engine/telemetry.py`` ``loop_span``) times the
phases of its step loop on ``time.monotonic_ns()`` and hands them to the
benchmark's ``stats_hook`` on every ``StepStats``: ``host_spans``, one flat
tuple ``name, t0_ns, t1_ns, name, t0_ns, t1_ns, ...`` (three values a span:
a tuple per span fed the cyclic collector a dozen objects a tick, PERF.md
section 6), and ``admit_wait_s``, queued -> admitted seconds of
each request admitted since the last ``StepStats``. The loop thread's spans
(``idle admit book step fetch emit reap publish yield``) tile a loop tick;
the step executor's (``pack upload launch sync``) lie inside ``step``.

The idle shares. Device 0's idle intervals in the traced sub-window that lie
OUTSIDE every program execution (``breakdown.idle_gaps``'s ``between_steps``
and ``no_request`` together; ``in_step`` idle is left out) are cut by the
spans, moved onto the trace's clock by the benchmark's marker
(``ctx.trace.lo - int(ctx.trace_host[0] * 1e9)``, as ``run.py`` moves the
requests). Where a loop-thread span and an executor span overlap, the
executor span wins. Each piece of idle time goes to exactly one group:

- ``schedule``: ``admit book reap publish``;
- ``emit``: ``emit`` (tokens to the callers' queues);
- ``yield``: ``yield idle``, and ``step`` outside the executor's spans (the
  hand-off between the loop thread and the executor thread);
- ``dispatch``: ``pack upload launch``, and the part of a gap inside a ``sync`` or
  ``fetch`` span that BEGAN AFTER the gap did (launch lag: the call has
  returned, the device has not started);
- ``readback``: the part of a gap inside a ``sync`` or ``fetch`` span that
  began before the gap did (the program has ended, the host still waits
  for its results);
- ``unattributed``: idle outside every span, the check on the
  instrumentation itself.

Each group is reported as % of the traced window, so the six groups plus the
``in_step`` share add up to device 0's ``device_idle``. A group that caught
no idle time reads 0.0. A profile's host and device clocks are off against
each other by a few tenths of a millisecond, differently in each profile
(PERF.md section 6, PR 24): that slides time between the two ends of every
gap, ``launch`` at its end and the waits at its start. So across runs compare
``dispatch + readback``; each alone only within one profile.

A program that predates the spans has nothing to read: every reader returns
``None``, which ``run.py`` leaves out of the line and ``contract.check_line``
then refuses, as it refuses any listed metric that is missing. That is why the
15 metrics are not listed in ``BENCHMARK.json`` yet: a PR may only add files
to the benchmark, and the traced run of its parent under the new files has to
print a line. ``host_spans.per_layer.json``, beside this file, holds the 15
entries for the ``benchmark`` PR that lets ``check_line`` pass over a metric
whose reader found nothing (PERF.md section 7).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmarks import trace_reduce
from benchmarks.metrics import _lib

GROUP_OF = {
    "admit": "schedule", "book": "schedule", "reap": "schedule", "publish": "schedule",
    "emit": "emit",
    "yield": "yield", "idle": "yield", "step": "yield",
    "pack": "dispatch", "upload": "dispatch", "launch": "dispatch",
}
WAITS = ("sync", "fetch")            # split into dispatch / readback by the gap's start
EXECUTOR = ("pack", "upload", "launch", "sync")
GROUPS = ("schedule", "emit", "yield", "dispatch", "readback", "unattributed")

Piece = Tuple[int, int, str, int]    # start, end, span name, the whole span's start


def _field(ctx, name: str) -> Optional[list]:
    """The field's values over the window's steps; ``None`` when no step has it."""
    found = [getattr(s, name) for _, s in ctx.steps if hasattr(s, name)]
    return found or None


def pieces_on_trace_clock(spans, shift: int, lo: int, hi: int) -> List[Piece]:
    """The spans as disjoint labelled pieces inside ``[lo, hi)``, sorted: the
    executor's spans whole, the loop thread's where no executor span lies."""
    ex: List[Piece] = []
    loop: List[Piece] = []
    for name, t0, t1 in spans:
        a, b = max(t0 + shift, lo), min(t1 + shift, hi)
        if b > a:
            (ex if name in EXECUTOR else loop).append((a, b, name, t0 + shift))
    free = trace_reduce.gaps(trace_reduce.merge((a, b) for a, b, _, _ in ex), lo, hi)
    out = list(ex)
    for a, b, name, start in loop:
        out.extend((x, y, name, start) for x, y in trace_reduce.intersect([(a, b)], free))
    return sorted(out)


def attribute(idle: List[Tuple[int, int]], pieces: List[Piece]) -> Dict[str, int]:
    """Nanoseconds of ``idle`` (sorted, disjoint gaps) that fall to each group."""
    total = dict.fromkeys(GROUPS, 0)
    i = 0
    for g0, g1 in idle:
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        j, inside = i, 0
        while j < len(pieces) and pieces[j][0] < g1:
            a, b, name, span_start = pieces[j]
            n = min(b, g1) - max(a, g0)
            if n > 0:
                if name in WAITS:
                    group = "dispatch" if span_start > g0 else "readback"
                else:
                    group = GROUP_OF.get(name)  # a name unknown here stays unattributed
                if group is not None:
                    total[group] += n
                    inside += n
            j += 1
        total["unattributed"] += (g1 - g0) - inside
    return total


def reduce(ctx) -> Optional[Dict[str, float]]:
    """Each group's share of the traced window in %, plus ``in_step``;
    ``None`` without a trace or without the field. Kept on ``ctx``."""
    if hasattr(ctx, "_host_span_shares"):
        return ctx._host_span_shares
    ctx._host_span_shares = None
    per_step = _field(ctx, "host_spans")
    red = ctx.trace
    if red is None or per_step is None:
        return None
    busy = trace_reduce.merge((s, s + d) for _, s, d in red.ops)
    idle = trace_reduce.gaps(busy, red.lo, red.hi)
    programs = trace_reduce.merge((s, s + d) for _, s, d in red.modules)
    between = trace_reduce.intersect(idle, trace_reduce.gaps(programs, red.lo, red.hi))
    in_step = trace_reduce.total_ns(trace_reduce.intersect(idle, programs))
    shift = red.lo - int(ctx.trace_host[0] * 1e9)
    spans = [sp for flat in per_step for sp in zip(flat[0::3], flat[1::3], flat[2::3])]
    ns = attribute(between, pieces_on_trace_clock(spans, shift, red.lo, red.hi))
    window = red.hi - red.lo
    shares = {g: 100.0 * n / window for g, n in ns.items()}
    shares["in_step"] = 100.0 * in_step / window
    ctx._host_span_shares = shares
    return shares


def idle_share(ctx, group: str) -> Optional[float]:
    shares = reduce(ctx)
    return None if shares is None else shares[group]


def admit_wait_ms(ctx, q: float) -> Optional[float]:
    """Percentile of queued -> admitted over the requests admitted in the window."""
    per_step = _field(ctx, "admit_wait_s")
    if per_step is None:
        return None
    return _lib.percentile([w * 1e3 for waits in per_step for w in waits], q)
