"""What the readers of pages by layer kind share (Command A+,
``cohere2_moe``): the attention launches' names and the steps that carry the
program's counters (``StepStats.page_groups_held``, ``.page_groups_released``
on the host; ``.win_keys_read``, ``.full_keys_read``, ``.win_decode_rows``,
``.full_decode_rows`` on a step's readback; PERF.md section 3). A program
without the counters gives none, and the readers return ``None``."""

from typing import List, Tuple

# every launch of the block's attention branch: the full layers' decode
# rows, and the ragged launch (plain and windowed) that serves the sliding
# layers' rows, a prefill chunk and a mixed step
ATTENTION = r"paged_decode_attention|ragged_paged_attention"


def grouped(steps) -> List[Tuple[float, object]]:
    """The steps of a program whose pages are kept by layer kind."""
    return [(t, s) for t, s in steps if getattr(s, "page_groups_held", None)]


def per_decode_row(ctx, keys: str, rows: str):
    """A counter of keys over its counter of rows (each summed over rows and
    the layers of its kind), over the window's steps: keys a decode row read
    in ONE layer of that kind."""
    steps = [s for _, s in ctx.steps if getattr(s, rows, None)]
    n = sum(getattr(s, rows) for s in steps)
    return sum(getattr(s, keys) for s in steps) / n if n else None


def decode_horizons(ctx) -> List[Tuple[float, object]]:
    """The window's decode horizons (``decode_steps`` steps each: the loop
    decodes step by step only while a request waits) that carry the routing
    counters of a held share."""
    return [(t, s) for t, s in grouped(ctx.steps)
            if s.phase == "decode" and s.queue_depth == 0
            and getattr(s, "moe_held_experts_touched", None) is not None]
