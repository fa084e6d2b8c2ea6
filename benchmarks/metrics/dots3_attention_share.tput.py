"""Share of the device's busy time in the traced sub-window spent in the
launches of both kinds' attention: ``paged_index_keys`` and
``sparse_latent_attention`` (the full layers: the index keys out of the pages,
the products over the selected keys) and ``windowed_latent_attention`` (the
sliding layers). The index scores and the top-k between the first two are
XLA's, inside ``fusion`` and ``sort``, and no reader tells them apart yet
(PERF.md section 7 gives them by hand). Read only from a program that counts
a windowed latent."""
from benchmarks.metrics import _dots3


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not _dots3.counted(ctx.steps_all):
        return None
    seconds = ctx.trace.op_seconds(_dots3.ATTENTION)
    return 100.0 * seconds / ctx.trace.busy_s if seconds > 0 else None
