"""Roofline share of the state-space mixer's decode recurrence (every
layer's launch named ``ssm_state_update``: decode horizons, single steps and
the decode rows of mixed steps), in the traced sub-window; bound: bytes.

Needed (``benchmarks/costs_ssm.py``): a live decode row reads its whole
recurrent state and writes it back, once a layer and step, and reads and
writes the token's own operands. The rows come from the program's own count
(``StepStats.ssm_rows_updated``: live decode rows x layers, a step) over the
steps that ended inside the sub-window; a horizon that straddles an edge is
counted whole or not at all. Over the HBM peak, over the summed device time
of the launches: the same work whatever implements it.
"""
from benchmarks import costs_ssm
from benchmarks.metrics import _ssm


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(_ssm.KERNEL)
    lo, hi = ctx.trace_host
    rows = sum(s.ssm_rows_updated for t, s in _ssm.counted(ctx.steps_all) if lo <= t < hi)
    if seconds <= 0 or not rows:
        return None
    need_s = costs_ssm.state_update_bytes(ctx.cfg, rows) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / seconds
