"""Median time from a program's end on the device to the arrival of its results on the host (stamped on the thread that learns it), on the estimated clock, over the joined executions that have an arrival; 0.0 from a program without the ledger. _launches.py."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.median_ms(ctx, "result_lags_ms")
