"""Keys a decode row read in ONE sliding layer, mean over the window's
decode rows (``StepStats.win_keys_read`` over ``.win_decode_rows``): the
window, once a context is longer than it, whatever the context's length."""
from benchmarks.metrics import _cmda


def read(ctx):
    return _cmda.per_decode_row(ctx, "win_keys_read", "win_decode_rows")
