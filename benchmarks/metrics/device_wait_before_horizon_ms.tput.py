"""Median idle of device 0 directly before an execution whose launch's program is decode_multi, decode or spec_multi. _launches.py; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.wait_ms(ctx, _launches.HORIZON_PROGRAMS)
