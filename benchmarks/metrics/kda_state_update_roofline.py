"""Roofline share of a KDA layer's decode recurrence (every KDA layer's
launch named ``kda_state_update``: decode horizons, single steps and the
decode rows of mixed steps), in the traced sub-window; bound: bytes.

Needed (``benchmarks/costs_kda.py``): a live decode row reads its whole
matrix state and writes it back, once a KDA layer and step, and reads and
writes the token's own operands. The rows come from the program's own count
(``StepStats.kda_rows_updated``: live decode rows x KDA layers, a step) over
the steps that ended inside the sub-window; a horizon that straddles an edge
is counted whole or not at all. Over the HBM peak, over the summed device
time of the launches: the same work whatever implements it.
"""
from benchmarks import costs_kda
from benchmarks.metrics import _kda


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(_kda.KERNEL)
    lo, hi = ctx.trace_host
    rows = sum(s.kda_rows_updated for t, s in _kda.counted(ctx.steps_all) if lo <= t < hi)
    if seconds <= 0 or not rows:
        return None
    need_s = costs_kda.state_update_bytes(ctx.cfg, rows) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / seconds
