"""Roofline share of the grouped expert multiplication; bound: bytes in a
decode step (each touched expert read once), FLOPs in a large chunk.

Needed, per step of the traced sub-window, from the program's two counters
(``StepStats.moe_experts_touched``, ``.moe_tokens_routed``):
``benchmarks/costs_moe.py`` ``grouped_matmul_least_s``, the larger of the
touched experts' bytes over the HBM peak and the routed rows' FLOPs over
the bf16 peak; summed, over the kernel's summed device time. A horizon's
counters are sums over its steps, so the larger of its two sums is no more
than the sum of its steps' larger: the share is never overstated by that. A
prefill-only step carries no counters (it has no readback): its kernel time
counts and its needed work does not.
"""
from benchmarks import costs_moe

KERNEL = r"moe_grouped_matmul"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(KERNEL)
    lo, hi = ctx.trace_host
    steps = [s for t, s in ctx.steps_all
             if lo <= t < hi and getattr(s, "moe_tokens_routed", None)]
    if seconds <= 0 or not steps:
        return None
    least_s = sum(
        costs_moe.grouped_matmul_least_s(ctx.cfg, s.moe_experts_touched, s.moe_tokens_routed, ctx.peaks)
        for s in steps
    )
    return 100.0 * least_s / seconds
