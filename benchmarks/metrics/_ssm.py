"""What the state-space readers share: the steps that carry the program's
recurrence counters (``StepStats.ssm_rows_updated``, ``.ssm_tokens_scanned``,
``.ssm_state_bytes``, ``.ssm_decode_steps``; PERF.md section 3). A program
without the counters gives none, and the readers return ``None``."""

from typing import List, Tuple

KERNEL = r"ssm_state_update"


def counted(steps) -> List[Tuple[float, object]]:
    return [(t, s) for t, s in steps if getattr(s, "ssm_rows_updated", None) is not None]
