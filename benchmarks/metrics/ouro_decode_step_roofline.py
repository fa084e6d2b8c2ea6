"""Roofline share of the looped decoder's decode horizon; bound: bytes. The
bound a later ``perf_opt`` on the cell is read against.

Needed bytes over the HBM peak, over the horizon program's device time in the
traced sub-window. Needed a horizon, from the program's counters and
``benchmarks/costs_ouro.py``: its steps (the engine's ``decode_steps``) x
the weights a step has to read (the layers' matrices ONCE A PASS, the head
once: ``weight_bytes_per_step``), the rows' keys and values once a slot
(``StepStats.ouro_slot_keys_read`` key positions, summed over rows, slots and
the horizon's steps, x a slot's bytes a key) and the fed tokens' keys and
values written once a slot (``ouro_stack_tokens`` x ``kv_bytes_per_token``):
the mean over the horizons read back in the sub-window. Device time: the
mean execution of ``decode_multi`` whole inside the sub-window. Discarded
steps of a row that finished inside a horizon are counted by the program as
read (about a hundredth of a request's steps). Read only from a program that
counts the passes.
"""
import statistics

from benchmarks import costs, costs_ouro
from benchmarks.metrics import _ouro


def read(ctx):
    if ctx.trace is None:
        return None
    steps = _ouro.in_subwindow(ctx, "decode")
    n, mean_s = _ouro.whole_executions(ctx, _ouro.HORIZON)
    if not steps or not n:
        return None
    weights = ctx.engine["decode_steps"] * costs_ouro.weight_bytes_per_step(ctx.cfg)
    slot_key = costs.kv_bytes_per_token_per_layer(ctx.cfg)
    needed = statistics.fmean(
        weights + s.ouro_slot_keys_read * slot_key
        + s.ouro_stack_tokens * costs_ouro.kv_bytes_per_token(ctx.cfg)
        for s in steps
    )
    return 100.0 * needed / (mean_s * ctx.peaks["hbm_bytes_per_s"])
