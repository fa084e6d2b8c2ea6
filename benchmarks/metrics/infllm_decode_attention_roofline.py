"""Roofline share of the sparse layers' decode attention (every sparse
layer's launch named ``infllm_decode_attention``: decode horizons, single
steps and the decode rows of mixed steps), in the traced sub-window; bound:
bytes.

Needed (``benchmarks/costs_sala.py``): a decode row reads, once a sparse
layer and step, the keys and values of the blocks each kv head chose, OF THAT
KV HEAD ALONE, and its query in and output out. The counts are the program's
own (``StepStats.infllm_keys_selected``, summed over the step's real decode
rows and the sparse layers; the rows from ``.lightning_rows_updated``, which
counts the same live rows over the lightning layers) over the steps that
ended inside the sub-window; a horizon that straddles an edge is counted
whole or not at all. A launch that copies whole pages reads the other kv
head's rows too and so reads at most half here. Over the HBM peak, over the
summed device time of the launches: the same work whatever implements it.
"""
from benchmarks import costs_sala
from benchmarks.metrics import _sala


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(_sala.ATTENTION_KERNEL)
    lo, hi = ctx.trace_host
    steps = [s for t, s in _sala.selection_counted(ctx.steps_all) if lo <= t < hi]
    if seconds <= 0 or not steps:
        return None
    rows = (sum(s.lightning_rows_updated or 0 for s in steps) / costs_sala.lightning_layers(ctx.cfg)
            * costs_sala.sparse_layers(ctx.cfg))
    need = costs_sala.decode_attention_bytes(
        ctx.cfg, sum(s.infllm_keys_selected for s in steps), rows)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / seconds
