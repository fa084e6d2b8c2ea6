"""Roofline share of the unified kernel's windowed launch (the sliding
layers, in decode rows, mixed steps and prefill chunks); bound: bytes.

Needed bytes over the HBM peak, over the kernel's summed device time in the
traced sub-window. Needed (``benchmarks/costs_moe.py``): a decode row reads
the keys and values of ``min(context, window)`` tokens in each call (one
call per sliding layer per step); a prefill chunk reads its own span and
the window before its first token, once per sliding layer. Decode rows'
contexts come from the request records as in
``paged_decode_attention_roofline``, clipped to the window and averaged over
the sub-window on the host clock; chunks from the requests whose prompt was
prefilled inside the sub-window (the uncached part, cut at the largest
prefill bucket). Whole 16-token pages, pages re-read per kv head and bucket
padding are the kernel's business.
"""
from benchmarks import costs_moe

KERNEL = r"ragged_paged_attention_windowed"


def mean_clipped_context_sum(ctx, samples: int = 400) -> float:
    lo, hi = ctx.trace_host
    total = 0.0
    for i in range(samples):
        t = lo + (hi - lo) * (i + 0.5) / samples
        for r in ctx.requests_all:
            if r["t_first"] is None or not (r["t_first"] <= t < r["t_last_or_end"]):
                continue
            emitted = sum(n for tc, n in zip(r["t_chunks"], r["n_chunks"]) if tc <= t)
            total += costs_moe.windowed_row_tokens(ctx.cfg, r["prompt_tokens"] + emitted)
    return total / samples


def chunk_tokens(ctx) -> float:
    """Keys the chunks prefilled inside the sub-window read, per sliding layer."""
    lo, hi = ctx.trace_host
    bucket = max(ctx.engine["prefill_buckets"])
    total = 0.0
    for r in ctx.requests_all:
        if r["cached_tokens"] is None or r["t_first"] is None:
            continue
        if not (lo <= r["t_ref"] and r["t_first"] <= hi):
            continue
        start = r["cached_tokens"]
        while start < r["prompt_tokens"]:
            n = min(bucket, r["prompt_tokens"] - start)
            total += costs_moe.windowed_row_tokens(ctx.cfg, start + n, n)
            start += n
    return total


def read(ctx):
    if ctx.trace is None:
        return None
    calls = ctx.trace.op_count(KERNEL)
    seconds = ctx.trace.op_seconds(KERNEL)
    if not calls or seconds <= 0:
        return None
    tokens = calls * mean_clipped_context_sum(ctx) + costs_moe.sliding_layers(ctx.cfg) * chunk_tokens(ctx)
    least_s = costs_moe.windowed_attention_bytes(ctx.cfg, tokens) / ctx.engine["tp"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
