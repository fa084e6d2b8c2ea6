"""Summaries a decode row read of its closed windows in one layer, mean over
the window's decode rows (``StepStats.eva_summaries_read`` over
``.eva_rows_attended``): ``window / chunk`` a closed window, so how many
windows a row has behind it (128 each at the published sizes)."""
from benchmarks.metrics import _eva


def read(ctx):
    return _eva.per_decode_row(ctx, "eva_summaries_read")
