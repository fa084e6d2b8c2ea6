"""Share of the traced window in which no operation ran on the device: 100 * (1 - busy_s / window_s)."""
from benchmarks.metrics._lib import idle_share


def read(ctx):
    return idle_share(ctx)
