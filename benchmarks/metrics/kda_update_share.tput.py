"""Share of the device's busy time in the traced sub-window spent in the
launches named ``kda_state_update``: whether the recurrence is most of the
work of a wide decode step."""
from benchmarks.metrics import _kda


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    seconds = ctx.trace.op_seconds(_kda.KERNEL)
    return 100.0 * seconds / ctx.trace.busy_s if seconds > 0 else None
