"""Exact keys a decode row read of its open window in one layer, mean over
the window's decode rows (``StepStats.eva_window_keys`` over
``.eva_rows_attended``): how full the ring is where the rows stand (half a
window at a steady state, whatever the context's length)."""
from benchmarks.metrics import _eva


def read(ctx):
    return _eva.per_decode_row(ctx, "eva_window_keys")
