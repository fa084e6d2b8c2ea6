"""Mean share of the paged cache held by running requests: kv_active_blocks / kv_total_blocks."""
from benchmarks.metrics._lib import step_mean


def read(ctx):
    return step_mean(ctx, lambda s: 100.0 * s.kv_active_blocks / s.kv_total_blocks)
