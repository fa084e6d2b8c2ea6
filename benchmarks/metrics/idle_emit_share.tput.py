"""Share of the traced window in which device 0 sat idle between two program executions while the host was in the loop's emit spans (accepted tokens to the callers' queues). _host_spans.py has the rule."""
from benchmarks.metrics import _host_spans


def read(ctx):
    return _host_spans.idle_share(ctx, "emit")
