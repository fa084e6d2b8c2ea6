"""Median idle of device 0 directly before an execution whose launch's program is mixed_step or prefill (a chunk-carrying step): the DEVICE's wait, where mixed_step_gap_ms is the host's turn since PR 42. _launches.py; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.wait_ms(ctx, _launches.CHUNK_PROGRAMS)
