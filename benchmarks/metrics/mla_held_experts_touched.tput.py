"""Held experts that got at least one row, mean per sparse layer per decode
step, over the window's decode horizons of a latent cache WITHOUT an indexer
(``StepStats.moe_held_experts_touched``: the layer holds one chip's share of
its experts and counts those only, under the group limit). The dense-latent
twin of ``moe_held_experts_touched.tput``, whose reader asks for an indexer's
counters and layer kinds."""
import statistics

from benchmarks import costs_mla
from benchmarks.metrics import _mla


def read(ctx):
    unit = costs_mla.sparse_layers(ctx.cfg) * ctx.engine["decode_steps"]
    vals = [s.moe_held_experts_touched / unit for _, s in _mla.decode_horizons(ctx)
            if getattr(s, "moe_held_experts_touched", None) is not None]
    return statistics.fmean(vals) if vals else None
