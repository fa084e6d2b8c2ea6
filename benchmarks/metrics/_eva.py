"""What the EVA readers share: the launches' names and the steps that carry
the program's counters (``StepStats.eva_rows_attended``, ``.eva_window_keys``,
``.eva_summaries_read``, ``.eva_windows_closed``, ``.eva_decode_steps``;
PERF.md section 3). A program without the counters gives none, and the
readers return ``None``."""

from typing import List, Tuple

KERNEL = r"eva_decode_attention"
# every launch that serves the family's attention: the decode rows' own, and
# the ragged launch a prefill chunk and a mixed step take
ATTENTION = r"eva_decode_attention|ragged_paged_attention"


def counted(steps) -> List[Tuple[float, object]]:
    return [(t, s) for t, s in steps if getattr(s, "eva_rows_attended", None)]


def per_decode_row(ctx, field: str):
    """A counter (summed over rows and layers) a decode row a layer, over
    the window's steps."""
    steps = counted(ctx.steps)
    rows = sum(s.eva_rows_attended for _, s in steps)
    return sum(getattr(s, field) for _, s in steps) / rows if rows else None
