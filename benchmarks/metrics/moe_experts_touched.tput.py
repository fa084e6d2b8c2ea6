"""Experts that got at least one row, mean per layer per decode step, over
the window's decode horizons (``StepStats.moe_experts_touched``)."""
import statistics

from benchmarks.metrics import _moe


def read(ctx):
    vals = [s.moe_experts_touched / _moe.layer_steps(ctx) for _, s in _moe.decode_horizons(ctx)]
    return statistics.fmean(vals) if vals else None
