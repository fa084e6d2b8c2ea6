"""Share of the device's busy time in the traced sub-window spent in the
launches of the block's attention branch (``paged_decode_attention`` for the
full layers' decode rows; ``ragged_paged_attention`` and
``ragged_paged_attention_windowed`` for the sliding layers' rows, a prefill
chunk and a mixed step): how much of a step the pages are, beside the
experts. Read only from a program whose pages are kept by layer kind."""
from benchmarks.metrics import _cmda


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not _cmda.grouped(ctx.steps_all):
        return None
    seconds = ctx.trace.op_seconds(_cmda.ATTENTION)
    return 100.0 * seconds / ctx.trace.busy_s if seconds > 0 else None
