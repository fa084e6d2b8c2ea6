"""idle_yield_share less the part of it under a submit or a deliver span: the loop gave its thread away while the device sat idle and no request says to whom. The check on the request spans, as idle_unattributed_share is on the loop's; the whole of idle_yield_share from a program that names nothing. _request_spans.py has the rule."""
from benchmarks.metrics import _request_spans


def read(ctx):
    return _request_spans.yield_part(ctx, "unnamed")
