"""Keys attended over as a share of the keys a decode row could see, over
the window's decode and mixed steps (``StepStats.dsa_keys_selected`` over
``.dsa_keys_causal``: real decode rows, summed over layers). 100 where the
selection does nothing (contexts under ``index_topk``, or a selection lost)."""
from benchmarks.metrics import _dsa


def read(ctx):
    steps = _dsa.counted(ctx.steps)
    causal = sum(s.dsa_keys_causal for _, s in steps)
    return 100.0 * sum(s.dsa_keys_selected for _, s in steps) / causal if causal else None
