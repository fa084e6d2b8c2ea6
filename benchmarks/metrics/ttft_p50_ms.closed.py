"""Time to first token from the send time, median over attempted requests. Not an end-to-end metric in the closed-loop cells: some tens of requests, spread over a decode horizon, move its median by about a tenth from run to run (PERF.md section 6)."""
from benchmarks.metrics._lib import ttft_ms


def read(ctx):
    return ttft_ms(ctx, 50)
