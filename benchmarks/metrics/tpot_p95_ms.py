"""Time per output token after the first, per request, 95th percentile across requests."""
from benchmarks.metrics._lib import tpot_ms


def read(ctx):
    return tpot_ms(ctx, 95)
