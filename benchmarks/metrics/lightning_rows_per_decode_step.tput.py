"""Live rows whose recurrence a decode step advanced, mean over the window's
decode steps (``StepStats.lightning_rows_updated`` over the lightning layers
run over ``.lightning_decode_steps``: a horizon's steps, one for a single or
mixed step): the check on the roofline's count; it should read what
``occupancy_mean.tput`` reads, less the rows still prefilling."""
from benchmarks import costs_sala
from benchmarks.metrics import _sala


def read(ctx):
    steps = _sala.state_counted(ctx.steps)
    n = sum(s.lightning_decode_steps or 0 for _, s in steps)
    if not n:
        return None
    return sum(s.lightning_rows_updated for _, s in steps) / costs_sala.lightning_layers(ctx.cfg) / n
