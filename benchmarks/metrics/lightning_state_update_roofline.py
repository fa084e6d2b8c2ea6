"""Roofline share of a lightning layer's decode recurrence (every lightning
layer's launch named ``lightning_state_update``: decode horizons, single
steps and the decode rows of mixed steps), in the traced sub-window; bound:
bytes.

Needed (``benchmarks/costs_sala.py``): a live decode row reads its whole
matrix state and writes it back, once a lightning layer and step, and reads
and writes the token's own operands. The rows come from the program's own
count (``StepStats.lightning_rows_updated``: live decode rows x lightning
layers, a step) over the steps that ended inside the sub-window; a horizon
that straddles an edge is counted whole or not at all. Over the HBM peak,
over the summed device time of the launches: the same work whatever
implements it.
"""
from benchmarks import costs_sala
from benchmarks.metrics import _sala


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(_sala.STATE_KERNEL)
    lo, hi = ctx.trace_host
    rows = sum(s.lightning_rows_updated for t, s in _sala.state_counted(ctx.steps_all) if lo <= t < hi)
    if seconds <= 0 or not rows:
        return None
    need_s = costs_sala.state_update_bytes(ctx.cfg, rows) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / seconds
