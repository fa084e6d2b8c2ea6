"""Queued to admitted (median over the requests admitted in the window), from the program's own stamps (StepStats.admit_wait_s): the part of time to first token spent waiting for a slot and for the running step. _host_spans.py."""
from benchmarks.metrics import _host_spans


def read(ctx):
    return _host_spans.admit_wait_ms(ctx, 50)
