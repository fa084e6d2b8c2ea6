"""Held experts that got at least one row, mean per routed layer per decode
step, over the window's decode horizons of a program whose pages are kept by
layer kind (``_cmda.decode_horizons``; ``StepStats.moe_held_experts_touched``:
a layer holds one chip's share of its experts and counts those only), over
THIS model's routed layers (4 of the 5 run)."""
import statistics

from benchmarks import costs_dots3
from benchmarks.metrics import _cmda


def read(ctx):
    unit = costs_dots3.routed_layers(ctx.cfg) * ctx.engine["decode_steps"]
    vals = [s.moe_held_experts_touched / unit for _, s in _cmda.decode_horizons(ctx)]
    return statistics.fmean(vals) if vals else None
