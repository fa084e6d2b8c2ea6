"""What the expert-routing readers share: the decode horizons of the window
that carry the program's routing counters (``StepStats.moe_*``, PERF.md
section 3). A program without the counters gives none, and the readers
return ``None``."""

from typing import List, Tuple


def layer_steps(ctx) -> int:
    """(layer, step) pairs a decode horizon sums its counters over."""
    return ctx.cfg["num_hidden_layers"] * ctx.engine["decode_steps"]


def decode_horizons(ctx) -> List[Tuple[float, object]]:
    """``(t, StepStats)`` of the window's decode horizons with counters. A
    horizon routes the same rows in each of its steps, so its routed count
    is a whole multiple of experts-per-token x layers x steps; a single-step
    decode (the loop's fallback while a request waits) is left out."""
    unit = ctx.cfg["num_experts_per_tok"] * layer_steps(ctx)
    return [
        (t, s) for t, s in ctx.steps
        if s.phase == "decode" and getattr(s, "moe_tokens_routed", None)
        and s.queue_depth == 0 and s.moe_tokens_routed % unit == 0
    ]
