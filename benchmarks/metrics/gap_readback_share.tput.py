"""Share of the traced window in which device 0 sat idle between a program's end and the arrival on the host of the results the next launch came after (the launch record's `after`), on the clock estimated from the launch ledger. _launches.py has the join, the offset and the split; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.share(ctx, "readback")
