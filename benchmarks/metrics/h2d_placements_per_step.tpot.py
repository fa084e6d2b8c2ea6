"""Mean StepStats.h2d_placements over the steps of the window: host values handed to a jitted call and per-slot arrays placed again, one transfer each (1 a steady synchronous step, 3 more where the chunk was not prebuilt, 0 a chained horizon)."""
from benchmarks.metrics._lib import step_mean


def read(ctx):
    return step_mean(ctx, lambda s: s.h2d_placements)
