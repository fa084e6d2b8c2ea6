"""How long the host takes to hand the device its next program once the last
one's results are in: what the ``mixed_step_gap_ms`` and ``horizon_gap_ms``
readers share. From ``StepStats.host_spans`` alone (``_host_spans.py``), over
the WHOLE window and without a device trace, which is what an operator has.

A gap runs from the end of the last ``sync`` or ``fetch`` span (the executor
or the loop has a program's results) to the end of the next ``launch`` span
(the jitted call of the next program has returned). It is filed under the
``phase`` of the ``StepStats`` that carries that ``launch``: ``mixed`` and
``prefill`` together (a chunk-carrying step: synchronous, the device idles
through the whole gap) or ``decode`` (a horizon: dispatched ahead where the
pipeline allows). A ``launch`` that follows another with no wait between
them, a pipeline topped up twice in one tick, has no results to count from
and is left out, as is the window's first.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

WAITS = ("sync", "fetch")
CHUNK_PHASES = ("mixed", "prefill")


def gaps_ms(ctx) -> Optional[Dict[str, List[float]]]:
    """The window's gaps in ms, ``chunk`` and ``decode``; ``None`` from a
    program without ``host_spans``. Kept on ``ctx``."""
    if hasattr(ctx, "_step_gaps"):
        return ctx._step_gaps
    ctx._step_gaps = None
    ends = []   # (t1_ns, name, the carrying step's phase)
    seen = False
    for _, s in ctx.steps:
        flat = getattr(s, "host_spans", None)
        if flat is None:
            continue
        seen = True
        ends.extend((t1, name, s.phase) for name, t1 in zip(flat[0::3], flat[2::3])
                    if name == "launch" or name in WAITS)
    if not seen:
        return None
    out: Dict[str, List[float]] = {"chunk": [], "decode": []}
    results_in = None
    for t1, name, phase in sorted(ends):
        if name in WAITS:
            results_in = t1
        elif results_in is not None:
            out["chunk" if phase in CHUNK_PHASES else "decode"].append((t1 - results_in) / 1e6)
            results_in = None
    ctx._step_gaps = out
    return out


def median_ms(ctx, kind: str) -> Optional[float]:
    gaps = gaps_ms(ctx)
    return statistics.median(gaps[kind]) if gaps and gaps[kind] else None
