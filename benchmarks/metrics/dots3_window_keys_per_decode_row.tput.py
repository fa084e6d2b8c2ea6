"""Keys a decode row read in ONE sliding layer, over the window's steps
(``StepStats.winlat_keys_read`` over ``.winlat_rows``, each summed over rows
and sliding layers): at most the window (513), whatever the context; the
launch copies up to a chunk of pages more and masks it."""
from benchmarks.metrics import _dots3


def read(ctx):
    steps = [s for _, s in _dots3.counted(ctx.steps)]
    rows = sum(s.winlat_rows for s in steps)
    return sum(s.winlat_keys_read for s in steps) / rows if rows else None
