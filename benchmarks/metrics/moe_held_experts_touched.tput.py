"""Held experts that got at least one row, mean per sparse layer per decode
step, over the window's decode horizons (``StepStats.moe_held_experts_touched``:
the layer holds one chip's share of its experts and counts those only)."""
import statistics

from benchmarks import costs_dsa
from benchmarks.metrics import _dsa


def read(ctx):
    unit = costs_dsa.sparse_layers(ctx.cfg) * ctx.engine["decode_steps"]
    vals = [s.moe_held_experts_touched / unit for _, s in _dsa.decode_horizons(ctx)
            if getattr(s, "moe_held_experts_touched", None) is not None]
    return statistics.fmean(vals) if vals else None
