"""Loop-thread time a request held before it was queued (its submit spans, summed; median over the requests queued in the window), from the program's own spans (StepStats.request_spans). 0.0 from a program that names no such time. _request_spans.py."""
from benchmarks.metrics import _request_spans


def read(ctx):
    return _request_spans.submit_p50_ms(ctx)
