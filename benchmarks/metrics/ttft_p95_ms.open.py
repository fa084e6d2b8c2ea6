"""Time to first token from the due time, 95th percentile over attempted requests. Not an end-to-end metric: too few requests lie beyond it in one window (PERF.md section 6)."""
from benchmarks.metrics._lib import ttft_ms


def read(ctx):
    return ttft_ms(ctx, 95)
