"""Passes of the stack a token went through, over the window's steps:
``StepStats.ouro_pass_tokens`` (a token counted once a pass) over
``ouro_stack_tokens`` (the tokens that entered the stack). ``total_ut_steps``
(4.0) while every token leaves at the last pass; the number an exit before it
would move. None from a program that counts no passes."""
from benchmarks.metrics import _ouro


def read(ctx):
    steps = _ouro.counted(ctx.steps)
    tokens = sum(s.ouro_stack_tokens for _, s in steps)
    return sum(s.ouro_pass_tokens for _, s in steps) / tokens if tokens else None
