"""Share of the device's busy time in the traced sub-window spent in the
launches of the looped decoder's attention: ``paged_decode_attention`` (the
decode rows, once a SLOT a step: 192 launches a step at the published sizes)
and ``ragged_paged_attention`` (a chunk and a mixed step's rows): what the
slots cost beside the four reads of the weights. Read only from a program
that counts the passes."""
from benchmarks.metrics import _ouro


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not _ouro.counted(ctx.steps_all):
        return None
    seconds = ctx.trace.op_seconds(_ouro.ATTENTION)
    return 100.0 * seconds / ctx.trace.busy_s if seconds > 0 else None
