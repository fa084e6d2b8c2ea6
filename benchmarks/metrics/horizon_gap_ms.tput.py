"""Host time from a program's results being in (the end of the last sync or fetch span) to the next launch having returned, median over the window's decode dispatches (StepStats.phase decode), from StepStats.host_spans alone. _step_gaps.py has the rule."""
from benchmarks.metrics import _step_gaps


def read(ctx):
    return _step_gaps.median_ms(ctx, "decode")
