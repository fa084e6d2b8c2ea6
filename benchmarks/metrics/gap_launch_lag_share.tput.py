"""Share of the traced window in which device 0 sat idle between the start of a launch's call and the start of THAT program on the device, on the clock estimated from the launch ledger (the fastest launch of the window defines zero). _launches.py has the split; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.share(ctx, "launch_lag")
