"""Live rows whose recurrence a decode step advanced, mean over the window's
decode steps (``StepStats.ssm_rows_updated`` over the layers run over
``.ssm_decode_steps``: a horizon's steps, one for a single or mixed step):
the check on the roofline's count; it should read what
``occupancy_mean.tput`` reads, less the rows still prefilling."""
from benchmarks.metrics import _ssm


def read(ctx):
    steps = _ssm.counted(ctx.steps)
    n = sum(s.ssm_decode_steps or 0 for _, s in steps)
    if not n:
        return None
    return sum(s.ssm_rows_updated for _, s in steps) / ctx.cfg["num_hidden_layers"] / n
