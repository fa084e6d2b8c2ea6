"""Width of the feasible interval of the profile's host/device clock offset, smallest (execution start - its launch's call start) less largest (execution end - its results' arrival) over the joined executions; negative if the interval is empty: a wrong pairing or a wrong stamp. _launches.py has the two inequalities; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.width_ms(ctx)
