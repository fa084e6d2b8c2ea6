"""Device time of one prefill chunk: median execution of the prefill and fused mixed programs in the trace."""
from benchmarks.metrics._lib import module_median_ms


def read(ctx):
    return module_median_ms(ctx, r"^jit_(prefill|mixed_step)\b")
