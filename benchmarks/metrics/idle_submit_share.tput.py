"""Share of the traced window in which device 0 sat idle between two program executions, the loop had given its thread away (the yield group of _host_spans.py) and a submit span held it: TpuEngine.generate's synchronous work before a request is queued (validation, the token list, the block hashes of the whole prompt). 0.0 from a program that names no such time. _request_spans.py has the rule."""
from benchmarks.metrics import _request_spans


def read(ctx):
    return _request_spans.yield_part(ctx, "submit")
