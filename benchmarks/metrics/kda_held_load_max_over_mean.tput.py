"""Largest row count on one held expert (max over a horizon's layers and
steps) over the mean rows a held expert gets in a layer of a step
(``StepStats.moe_load_max`` x held experts x layers x steps over
``.moe_tokens_routed``, which count the held experts and the rows routed to
them only), mean over the window's decode horizons: 1.0 would be an even
spread over this chip's share. The twin of ``moe_load_max_over_mean.tput``,
whose reader takes a horizon by a whole layer's routed count."""
import statistics

from benchmarks.metrics import _kda


def read(ctx):
    cells = ctx.cfg["n_routed_experts"] * _kda.layer_steps(ctx)
    vals = [s.moe_load_max * cells / s.moe_tokens_routed
            for _, s in _kda.decode_horizons(ctx) if s.moe_tokens_routed]
    return statistics.fmean(vals) if vals else None
