"""Live rows whose recurrence a decode step advanced, mean over the window's
decode steps (``StepStats.kda_rows_updated`` over the KDA layers run over
``.kda_decode_steps``: a horizon's steps, one for a single or mixed step):
the check on the roofline's count; it should read what
``occupancy_mean.tput`` reads, less the rows still prefilling."""
from benchmarks import costs_kda
from benchmarks.metrics import _kda


def read(ctx):
    steps = _kda.counted(ctx.steps)
    n = sum(s.kda_decode_steps or 0 for _, s in steps)
    if not n:
        return None
    return sum(s.kda_rows_updated for _, s in steps) / costs_kda.kda_layers(ctx.cfg) / n
