"""To whom the loop gave its thread: the ``yield`` group of the idle shares
(``_host_spans.py``) cut by the spans that name a request, and how long the
loop thread was held before a request was queued. What the
``idle_submit_share``, ``idle_deliver_share``, ``idle_yield_unnamed_share``
and ``submit_p50_ms`` readers share, done once per run and kept on ``ctx``.

The program (``dynamo_tpu/engine/telemetry.py``) hands the benchmark's
``stats_hook`` a second flat field on every ``StepStats``, ``request_spans``:
``name, t0_ns, t1_ns, request_id`` of each span with a request for a subject
that ended since the last one, on the loop's clock (``time.monotonic_ns()``).
``submit`` is ``TpuEngine.generate``'s synchronous work from its entry to the
request being queued (validation, the token list, the block hashes of the
whole prompt), cut at every ``await``; ``deliver`` runs from a result leaving
the request's queue to the caller asking for the next one. Both run on the
event-loop thread, which is the step loop's: inside its ``yield`` and
``idle`` spans and the awaits of ``step`` and ``fetch``.

The shares. ``_host_spans.py`` puts device 0's between-step idle time under
``yield``, ``idle`` and ``step`` outside the executor's spans into its
``yield`` group. ONLY those pieces are cut again here: under a ``submit``
span, under a ``deliver`` span where no ``submit`` span lies (a caller that
awaits behind its ``yield`` holds its ``deliver`` open while others run), and
the rest, ``unnamed``: the loop gave the thread away and no request says to
whom. The three add up to ``idle_yield_share``; idle time under a ``fetch``
or ``sync`` wait stays readback or dispatch whatever ran beside it.

A program that predates ``request_spans`` names no such time: ``submit`` and
``deliver`` read 0.0, ``unnamed`` the whole of ``idle_yield_share``, and
``submit_p50_ms`` 0.0. Those zeros say "not named", not "free": a reader here
never returns ``None`` where ``idle_yield_share`` has a number (a ``None``
drops the metric and the line with it, ``_host_spans.py``).
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks import trace_reduce
from benchmarks.metrics import _host_spans

PARTS = ("submit", "deliver", "unnamed")
Quad = Tuple[str, int, int, str]


def quads(ctx) -> List[Quad]:
    """The window's ``request_spans``; none from a program without the field."""
    per_step = _host_spans._field(ctx, "request_spans") or []
    return [q for flat in per_step for q in zip(flat[0::4], flat[1::4], flat[2::4], flat[3::4])]


def reduce(ctx) -> Optional[Dict[str, float]]:
    """``submit``, ``deliver`` and ``unnamed`` in % of the traced window, and
    their sum ``yield``; ``None`` where ``_host_spans.reduce`` is."""
    if hasattr(ctx, "_yield_parts"):
        return ctx._yield_parts
    ctx._yield_parts = None
    if _host_spans.reduce(ctx) is None:
        return None
    red = ctx.trace
    # the gaps and the pieces as _host_spans.reduce makes them
    busy = trace_reduce.merge((s, s + d) for _, s, d in red.ops)
    programs = trace_reduce.merge((s, s + d) for _, s, d in red.modules)
    between = trace_reduce.intersect(trace_reduce.gaps(busy, red.lo, red.hi),
                                     trace_reduce.gaps(programs, red.lo, red.hi))
    shift = red.lo - int(ctx.trace_host[0] * 1e9)
    flat = [sp for f in _host_spans._field(ctx, "host_spans") for sp in zip(f[0::3], f[1::3], f[2::3])]
    pieces = _host_spans.pieces_on_trace_clock(flat, shift, red.lo, red.hi)
    given_away = trace_reduce.merge(
        (a, b) for a, b, name, _ in pieces if _host_spans.GROUP_OF.get(name) == "yield")
    idle = trace_reduce.intersect(between, given_away)

    named = [(n, max(t0 + shift, red.lo), min(t1 + shift, red.hi)) for n, t0, t1, _ in quads(ctx)]

    def held(name: str) -> List[Tuple[int, int]]:
        return trace_reduce.merge((a, b) for n, a, b in named if n == name and b > a)

    submit = held("submit")
    deliver = trace_reduce.intersect(held("deliver"), trace_reduce.gaps(submit, red.lo, red.hi))
    ns = {"yield": trace_reduce.total_ns(idle),
          "submit": trace_reduce.total_ns(trace_reduce.intersect(idle, submit)),
          "deliver": trace_reduce.total_ns(trace_reduce.intersect(idle, deliver))}
    ns["unnamed"] = ns["yield"] - ns["submit"] - ns["deliver"]
    window = red.hi - red.lo
    ctx._yield_parts = {k: 100.0 * v / window for k, v in ns.items()}
    return ctx._yield_parts


def yield_part(ctx, part: str) -> Optional[float]:
    parts = reduce(ctx)
    return None if parts is None else parts[part]


def submit_p50_ms(ctx) -> Optional[float]:
    """Median, over the requests queued in the window, of the loop-thread
    time their ``submit`` spans held together; 0.0 where none is named."""
    if _host_spans._field(ctx, "host_spans") is None:
        return None  # not this program's StepStats at all
    per_request: Dict[str, int] = {}
    for name, t0, t1, rid in quads(ctx):
        if name == "submit":
            per_request[rid] = per_request.get(rid, 0) + (t1 - t0)
    return statistics.median(per_request.values()) / 1e6 if per_request else 0.0
