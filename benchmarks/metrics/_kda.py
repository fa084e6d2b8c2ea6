"""What the KDA readers share: the steps that carry the program's recurrence
counters (``StepStats.kda_rows_updated``, ``.kda_tokens_scanned``,
``.kda_decode_steps``; PERF.md section 3). A program without the counters
gives none, and the readers return ``None``."""

from typing import List, Tuple

KERNEL = r"kda_state_update"


def counted(steps) -> List[Tuple[float, object]]:
    return [(t, s) for t, s in steps if getattr(s, "kda_rows_updated", None) is not None]


def decode_horizons(ctx) -> List[Tuple[float, object]]:
    """The window's decode horizons (``decode_steps`` steps each: the loop
    decodes step by step only while a request waits) that carry the routing
    counters of a held share beside the recurrence's."""
    return [(t, s) for t, s in counted(ctx.steps)
            if s.phase == "decode" and s.queue_depth == 0
            and getattr(s, "moe_held_experts_touched", None) is not None]


def layer_steps(ctx) -> int:
    """(layer, step) pairs a decode horizon sums its routing counters over:
    every layer held routes."""
    return ctx.cfg["num_hidden_layers"] * ctx.engine["decode_steps"]
