"""What MiniCPM-SALA's readers share: the launches' names and the steps that
carry the program's counters (``StepStats.lightning_rows_updated``,
``.lightning_tokens_scanned``, ``.lightning_decode_steps``;
``.infllm_keys_selected``, ``.infllm_keys_causal``, ``.infllm_rows_sparse``,
``.infllm_pooled_keys_written``; PERF.md section 3). A program without the
counters gives none, and the readers return ``None``."""

from typing import List, Tuple

STATE_KERNEL = r"lightning_state_update"
ATTENTION_KERNEL = r"infllm_decode_attention"


def state_counted(steps) -> List[Tuple[float, object]]:
    return [(t, s) for t, s in steps if getattr(s, "lightning_rows_updated", None) is not None]


def selection_counted(steps) -> List[Tuple[float, object]]:
    return [(t, s) for t, s in steps if getattr(s, "infllm_keys_causal", None)]


def kernel_share(ctx, kernel: str):
    """Share of the device's busy time in the traced sub-window spent in the
    launches named ``kernel``."""
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    seconds = ctx.trace.op_seconds(kernel)
    return 100.0 * seconds / ctx.trace.busy_s if seconds > 0 else None
