"""Roofline share of the paged decode attention kernel; bound: bytes.

Needed bytes over the HBM peak, over the kernel's summed device time in the
traced sub-window. Needed: every call (one per layer per decode step) has
to read the keys and values of its rows' contexts once,
``benchmarks/costs.py`` ``decode_attention_bytes``. The rows' contexts come
from the request records (prompt + tokens emitted so far, for requests
between their first and last token), averaged over the sub-window on the
host clock. Pages re-read per kv head, padding and discarded horizon steps
are the kernel's and the program's business: they take time and add no
needed byte.
"""
from benchmarks import costs

KERNEL = r"paged_decode_attention"


def mean_context_sum(ctx, samples: int = 400) -> float:
    lo, hi = ctx.trace_host
    total = 0.0
    for i in range(samples):
        t = lo + (hi - lo) * (i + 0.5) / samples
        for r in ctx.requests_all:
            if r["t_first"] is None or not (r["t_first"] <= t < r["t_last_or_end"]):
                continue
            emitted = sum(n for tc, n in zip(r["t_chunks"], r["n_chunks"]) if tc <= t)
            total += r["prompt_tokens"] + emitted
    return total / samples


def read(ctx):
    if ctx.trace is None:
        return None
    calls = ctx.trace.op_count(KERNEL)
    seconds = ctx.trace.op_seconds(KERNEL)
    if not calls or seconds <= 0:
        return None
    per_call = costs.decode_attention_bytes(ctx.cfg, mean_context_sum(ctx)) / ctx.engine["tp"]
    least_s = calls * per_call / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
