"""Mean StepStats.queue_depth (requests waiting for admission) over the steps of the window."""
from benchmarks.metrics._lib import step_mean


def read(ctx):
    return step_mean(ctx, lambda s: s.queue_depth)
