"""Share of the bf16 peak the chunk-carrying steps reach: the cell's share of
the whole step (a turn is about three quarters chunk time).

Needed FLOPs of a chunk-carrying step over (its device time x the peak).
Needed per token (``benchmarks/costs_dots3.py`` ``chunk_flops``): the
matrices by layer kind, with the routed experts a token chose of those held
here, and the attention of both kinds at the requests' own contexts (the full
layers' index scores over every causal key and their selected products, the
sliding layers' windowed products), worked out from the records of the
requests prefilled in the window; times ``StepStats.tokens`` of the prefill
and mixed steps in the traced sub-window (a mixed step's decode rows are
tokens through the same matrices). Device time: mean execution of the prefill
and mixed programs whole inside the sub-window. Padding to the bucket is the
program's: it takes time and adds no needed FLOP. Read only from a program
that counts a windowed latent.
"""
import statistics

from benchmarks import costs_dots3
from benchmarks.metrics import _dots3

PROGRAMS = r"^jit_(prefill|mixed_step)\b"


def read(ctx):
    if ctx.trace is None or not _dots3.counted(ctx.steps_all):
        return None
    durs = ctx.trace.module_durations_s(PROGRAMS)
    lo, hi = ctx.trace_host
    toks = [s.tokens for t, s in ctx.steps_all
            if s.phase in ("prefill", "mixed") and lo <= t < hi]
    if not durs or not toks:
        return None
    flops = new_tokens = 0.0
    for r in ctx.requests:
        if r["cached_tokens"] is None:
            continue
        new = r["prompt_tokens"] - r["cached_tokens"]
        flops += costs_dots3.chunk_flops(ctx.cfg, new, r["cached_tokens"])
        new_tokens += new
    if new_tokens <= 0:
        return None
    needed = statistics.fmean(toks) * flops / new_tokens
    return 100.0 * needed / (statistics.fmean(durs) * ctx.peaks["bf16_flops_per_s"])
