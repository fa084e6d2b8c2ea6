"""Roofline share of EVA's decode attention (every layer's launch named
``eva_decode_attention``: decode horizons and single decode steps), in the
traced sub-window; bound: bytes.

Needed (``benchmarks/costs_eva.py``): a decode row reads, once a layer and
step, the keys and values of its open window up to its position and one
summary a chunk of its closed windows, every head's. The counts are the
program's own (``StepStats.eva_window_keys``, ``.eva_summaries_read``,
``.eva_rows_attended``: summed over the step's real decode rows and the
layers) over the ``decode`` steps that ended inside the sub-window (a mixed
step's decode rows ride the ragged launch beside the chunk and are not this
kernel's); a horizon that straddles an edge is counted whole or not at all.
Over the HBM peak, over the summed device time of the launches: the same
work whatever implements it.
"""
from benchmarks import costs_eva
from benchmarks.metrics import _eva


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(_eva.KERNEL)
    lo, hi = ctx.trace_host
    steps = [s for t, s in _eva.counted(ctx.steps_all) if lo <= t < hi and s.phase == "decode"]
    if seconds <= 0 or not steps:
        return None
    need = costs_eva.decode_attention_bytes(
        ctx.cfg, sum(s.eva_window_keys for s in steps), sum(s.eva_summaries_read for s in steps),
        sum(s.eva_rows_attended for s in steps))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / seconds
