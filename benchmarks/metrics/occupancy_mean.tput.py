"""Mean StepStats.batch_occupancy over the decode and mixed steps of the window."""
from benchmarks.metrics._lib import step_mean


def read(ctx):
    return step_mean(ctx, lambda s: s.batch_occupancy, ("decode", "mixed"))
