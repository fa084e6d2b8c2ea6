"""Held experts that got at least one row, mean per layer per decode step,
over the window's decode horizons of the family that carries the ``kda_*``
counters (``StepStats.moe_held_experts_touched``: every layer routes and holds
one chip's share of its experts, ``n_routed_experts`` of the configuration's
file, and counts those only). The twin of ``mla_held_experts_touched.tput``,
whose reader asks for a latent's counters: the weights a step's grouped
multiplication has to read."""
import statistics

from benchmarks.metrics import _kda


def read(ctx):
    unit = _kda.layer_steps(ctx)
    vals = [s.moe_held_experts_touched / unit for _, s in _kda.decode_horizons(ctx)]
    return statistics.fmean(vals) if vals else None
