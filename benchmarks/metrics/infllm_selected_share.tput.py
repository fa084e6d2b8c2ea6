"""Keys a decode row's launch was HANDED as a share of the causal keys it
could have read, over the window's decode and mixed steps
(``StepStats.infllm_keys_selected``, the lengths of the views the seam gave
each sparse layer's decode launch, a kv head, over ``.infllm_keys_causal``:
real decode rows, summed over the sparse layers). 100 where no context is
past ``dense_len``, or where the launches were handed every key."""
from benchmarks.metrics import _sala


def read(ctx):
    steps = _sala.selection_counted(ctx.steps)
    causal = sum(s.infllm_keys_causal for _, s in steps)
    return 100.0 * sum(s.infllm_keys_selected for _, s in steps) / causal if causal else None
