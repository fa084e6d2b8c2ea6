"""Share of the traced window in which device 0 sat idle between two program executions while the host was in the executor's pack, upload and launch spans, and the part of a gap inside a sync or fetch span that began after the gap did (launch lag). _host_spans.py has the rule."""
from benchmarks.metrics import _host_spans


def read(ctx):
    return _host_spans.idle_share(ctx, "dispatch")
