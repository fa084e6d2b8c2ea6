"""Share of the window's mixed steps with StepStats.mixed_chained true: launched on the device carry of the mixed step before them, before the loop had read that one (0.0 from a program without the field: it chains none)."""
from benchmarks.metrics._mixed_chain import share


def read(ctx):
    return share(ctx)
