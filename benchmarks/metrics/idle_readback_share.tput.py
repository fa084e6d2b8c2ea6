"""Share of the traced window in which device 0 sat idle between two program executions while the host was in a sync or fetch span that began before the gap did (the program has ended, the host still waits for its results). _host_spans.py has the rule."""
from benchmarks.metrics import _host_spans


def read(ctx):
    return _host_spans.idle_share(ctx, "readback")
