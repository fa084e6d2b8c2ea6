"""Time to first token from the due time, median over attempted requests. Not an end-to-end metric: it moves by 3-10% between two runs of the same requests (PERF.md section 6)."""
from benchmarks.metrics._lib import ttft_ms


def read(ctx):
    return ttft_ms(ctx, 50)
