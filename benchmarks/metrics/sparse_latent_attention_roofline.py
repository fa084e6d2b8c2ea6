"""Roofline share of latent attention over selected keys (every layer's
launch, in decode rows, mixed steps and prefill chunks); bound: bytes.

Needed (``benchmarks/costs_dsa.py``): every (query, selected key) pair reads
one latent row, over the HBM peak; over the summed device time of the launch
named ``sparse_latent_attention`` in the traced sub-window. Decode rows'
pairs are the program's own count (``StepStats.dsa_keys_selected``: real
decode rows x layers x ``min(context, index_topk)``) over the steps that
ended inside the sub-window; a horizon that straddles an edge is counted
whole or not at all. A chunk's queries are not in that counter: they come
from the requests whose prompt was prefilled inside the sub-window (the
uncached part, token by token at its position, once a layer).
"""
from benchmarks import costs_dsa
from benchmarks.metrics import _dsa

KERNEL = r"sparse_latent_attention"


def chunk_keys(ctx) -> float:
    """(query, key) pairs of the chunks prefilled inside the sub-window, one layer."""
    lo, hi = ctx.trace_host
    topk = ctx.cfg["index_topk"]

    def upto(n):  # sum over positions p < n of min(p + 1, topk)
        m = min(n, topk)
        return m * (m + 1) / 2 + (n - m) * topk

    total = 0.0
    for r in ctx.requests_all:
        if r["cached_tokens"] is None or r["t_first"] is None:
            continue
        if not (lo <= r["t_ref"] and r["t_first"] <= hi):
            continue
        total += upto(r["prompt_tokens"]) - upto(r["cached_tokens"])
    return total


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(KERNEL)
    lo, hi = ctx.trace_host
    steps = [s for t, s in _dsa.counted(ctx.steps_all) if lo <= t < hi]
    if seconds <= 0 or not steps:
        return None
    keys = sum(s.dsa_keys_selected for s in steps) + ctx.cfg["num_hidden_layers"] * chunk_keys(ctx)
    return 100.0 * costs_dsa.sparse_attention_least_s(ctx.cfg, keys, ctx.peaks) / seconds
