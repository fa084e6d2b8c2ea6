"""Largest row count on one expert (max over a horizon's layers and steps)
over the mean rows an expert gets in a layer of a step, mean over the
window's decode horizons: 1.0 would be an even spread."""
import statistics

from benchmarks.metrics import _moe


def read(ctx):
    cells = ctx.cfg["num_experts"] * _moe.layer_steps(ctx)
    vals = [s.moe_load_max * cells / s.moe_tokens_routed for _, s in _moe.decode_horizons(ctx)]
    return statistics.fmean(vals) if vals else None
