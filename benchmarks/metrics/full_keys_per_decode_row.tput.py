"""Keys a decode row read in ONE full layer, mean over the window's decode
rows (``StepStats.full_keys_read`` over ``.full_decode_rows``): the whole
context, which only the full layers' page group still holds."""
from benchmarks.metrics import _cmda


def read(ctx):
    return _cmda.per_decode_row(ctx, "full_keys_read", "full_decode_rows")
