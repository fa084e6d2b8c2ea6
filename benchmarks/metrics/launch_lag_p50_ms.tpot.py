"""Median time from the start of a launch's call to the start of its program on the device, on the estimated clock, over the launches made onto an idle device (one queued behind a running program measures that program); 0.0 where no launch found the device idle, and from a program without the ledger. _launches.py."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.median_ms(ctx, "launch_lags_ms")
