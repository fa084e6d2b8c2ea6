"""Share of the traced window in which device 0 sat idle between two program executions while the host was in no span at all: the check on the instrumentation itself. _host_spans.py has the rule."""
from benchmarks.metrics import _host_spans


def read(ctx):
    return _host_spans.idle_share(ctx, "unattributed")
