"""Roofline share of latent attention under a window (the sliding layers'
launch, in decode rows, mixed steps and prefill chunks); bound: bytes for
decode rows, products for a chunk.

Needed (``benchmarks/costs_dots3.py`` ``windowed_least_s``), over the summed
device time of the launch named ``windowed_latent_attention`` in the traced
sub-window. Decode rows' keys are the program's own count
(``StepStats.winlat_keys_read``: real decode rows x sliding layers x
``min(context, window)``) over the steps that ended inside the sub-window; a
horizon that straddles an edge is counted whole or not at all. A chunk's
queries are not in that counter: their visible pairs come from the requests
whose prompt was prefilled inside the sub-window (the uncached part, token by
token at its position, once a sliding layer).
"""
from benchmarks import costs_dots3
from benchmarks.metrics import _dots3


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(_dots3.WINDOWED)
    lo, hi = ctx.trace_host
    steps = [s for t, s in _dots3.counted(ctx.steps_all) if lo <= t < hi]
    if seconds <= 0 or not steps:
        return None
    n_win = costs_dots3.layers_of_kind(ctx.cfg, costs_dots3.SLIDING)
    pairs = keys = 0.0
    for new, cached in _dots3.prefilled_in_subwindow(ctx):
        pairs += costs_dots3.window_pairs(ctx.cfg, new, cached)
        keys += new + min(cached, ctx.cfg["sliding_window_size"] - 1)
    least_s = costs_dots3.windowed_least_s(
        ctx.cfg, sum(s.winlat_keys_read for s in steps), n_win * pairs, n_win * keys, ctx.peaks)
    return 100.0 * least_s / seconds
