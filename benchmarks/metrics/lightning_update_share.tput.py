"""Share of the device's busy time in the traced sub-window spent in the
launches named ``lightning_state_update``: how much of a decode step the
recurrence of the lightning layers is."""
from benchmarks.metrics import _sala


def read(ctx):
    return _sala.kernel_share(ctx, _sala.STATE_KERNEL)
