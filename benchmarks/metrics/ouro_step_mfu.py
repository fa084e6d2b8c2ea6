"""Share of the bf16 peak the looped decoder's steps reach: the cell's share
of the WHOLE step (decode horizons and mixed steps are the window).

Needed FLOPs of the step programs executed whole in the traced sub-window
over (their device time x the peak). Needed, from the program's counters and
``benchmarks/costs_ouro.py`` ``step_flops``: ``StepStats.ouro_stack_tokens``
(the real tokens that entered the stack: a chunk's and the decode rows) x 2
FLOPs a matrix weight a token a PASS, the decode rows' attention over
``ouro_slot_keys_read`` key positions, a mixed step's chunk against its own
causal keys in every slot (its ``tokens`` less its decode rows, a new
prompt: nothing is shared in the cell), and the head a sampled row. A kind of
program (the horizon, the mixed step) counts as many times as it ran whole
in the sub-window, at the mean need of that kind's steps there and its mean
device time. It reads LOW, a few percent: 8 rows cannot fill the matrix
unit, and a step's floor is the weights' bytes (``ouro_decode_step_roofline``).
Bucket padding and discarded horizon steps are the program's: they take time
and add no needed FLOP. A prefill alone carries no counters and is left out
(none runs while a decode row is resident). Read only from a program that
counts the passes.
"""
import statistics

from benchmarks import costs_ouro
from benchmarks.metrics import _ouro


def read(ctx):
    if ctx.trace is None or not _ouro.counted(ctx.steps_all):
        return None
    rows = ctx.engine["max_batch_size"]
    needed = seconds = 0.0
    for phase, pattern in (("decode", _ouro.HORIZON), ("mixed", _ouro.MIXED)):
        steps = _ouro.in_subwindow(ctx, phase)
        n, mean_s = _ouro.whole_executions(ctx, pattern)
        if not steps or not n:
            continue
        flops = []
        for s in steps:
            keys, sampled = s.ouro_slot_keys_read, s.ouro_stack_tokens
            if phase == "mixed":
                # the rows beside the chunk: the slots in use but the chunk's
                decode = max(min(s.batch_occupancy, rows) - 1, 0)
                chunk = max(s.ouro_stack_tokens - decode, 0)
                keys += costs_ouro.chunk_slot_keys(ctx.cfg, chunk, 0)
                sampled = decode + 1
            flops.append(costs_ouro.step_flops(ctx.cfg, s.ouro_stack_tokens, keys, sampled))
        needed += n * statistics.fmean(flops)
        seconds += n * mean_s
    if seconds <= 0:
        return None
    return 100.0 * needed / (seconds * ctx.peaks["bf16_flops_per_s"])
