"""Device time of one decode step: median execution of the decode horizon program in the trace, over the steps it runs."""
from benchmarks.metrics._lib import module_median_ms


def read(ctx):
    return module_median_ms(ctx, r"^jit_decode_multi\b", ctx.engine["decode_steps"])
