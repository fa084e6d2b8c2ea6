"""Share of the bf16 peak the prefill chunks reach (ISSUE 23's prefill_mxu_share).

Needed FLOPs of a chunk-carrying step over (its device time x the peak).
Needed per step, from the program's counters: StepStats.tokens of the
prefill and mixed steps in the traced sub-window (a mixed step's decode rows
are tokens through the same matrices), 2 FLOPs per matrix weight per token
(``benchmarks/costs.py``), scaled by the cell's ratio of causal-attention
to matrix FLOPs worked out from the request records. The output head (one
row per sampled token) is left out: under 0.01% of a chunk. Device time:
mean execution of the prefill and mixed programs whole inside the
sub-window. Padding to the bucket is the program's: it takes time and adds
no needed FLOP.
"""
import statistics

from benchmarks import costs

PROGRAMS = r"^jit_(prefill|mixed_step)\b"


def read(ctx):
    if ctx.trace is None:
        return None
    durs = ctx.trace.module_durations_s(PROGRAMS)
    lo, hi = ctx.trace_host
    toks = [s.tokens for t, s in ctx.steps_all
            if s.phase in ("prefill", "mixed") and lo <= t < hi]
    if not durs or not toks:
        return None
    mm = attn = 0.0
    for r in ctx.requests:
        if r["cached_tokens"] is None:
            continue
        new = r["prompt_tokens"] - r["cached_tokens"]
        whole = costs.prefill_flops(ctx.cfg, new, r["cached_tokens"])
        only_mm = 2.0 * new * costs.matmul_params_per_layer(ctx.cfg) * ctx.cfg["num_hidden_layers"]
        mm += only_mm
        attn += whole - only_mm
    if mm <= 0:
        return None
    per_token = 2.0 * costs.matmul_params_per_layer(ctx.cfg) * ctx.cfg["num_hidden_layers"] * (1.0 + attn / mm)
    needed = statistics.fmean(toks) * per_token / ctx.engine["tp"]
    return 100.0 * needed / (statistics.fmean(durs) * ctx.peaks["bf16_flops_per_s"])
