"""Share of the traced window in which device 0 sat idle between two program executions, the loop had given its thread away (the yield group of _host_spans.py) and a deliver span held it where no submit span did: a result handed to its caller, the engine's own per-item work and the caller's code behind the yield. 0.0 from a program that names no such time. _request_spans.py has the rule."""
from benchmarks.metrics import _request_spans


def read(ctx):
    return _request_spans.yield_part(ctx, "deliver")
