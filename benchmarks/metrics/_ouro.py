"""What the readers of the looped decoder (``ouro``) share: the step
programs, the launches of its attention and the steps that carry the
program's counters (``StepStats.ouro_stack_tokens``, ``.ouro_pass_tokens``,
``.ouro_slot_keys_read`` on a decode or mixed step's readback; PERF.md
section 3). A program without the counters gives none, and the readers
return ``None``."""

import statistics
from typing import List, Optional, Tuple

HORIZON = r"^jit_decode_multi\b"
MIXED = r"^jit_mixed_step\b"
# the decode rows' launch and the ragged launch a chunk and a mixed step take
ATTENTION = r"paged_decode_attention|ragged_paged_attention"


def counted(steps) -> List[Tuple[float, object]]:
    """The steps that carry the family's counters."""
    return [(t, s) for t, s in steps if getattr(s, "ouro_stack_tokens", None)]


def in_subwindow(ctx, phase: str) -> List[object]:
    """The counted steps of ``phase`` whose readback fell in the traced
    sub-window (the host's clock)."""
    lo, hi = ctx.trace_host
    return [s for t, s in counted(ctx.steps_all) if s.phase == phase and lo <= t < hi]


def whole_executions(ctx, pattern: str) -> Tuple[int, Optional[float]]:
    """(how many, their mean device seconds) of the executions of
    ``pattern`` whole inside the sub-window."""
    durs = ctx.trace.module_durations_s(pattern)
    return len(durs), (statistics.fmean(durs) if durs else None)
