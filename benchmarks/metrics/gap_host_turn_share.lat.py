"""Share of the traced window in which device 0 sat idle between the arrival of the results the next launch came after and the start of that launch's call: the host's turn, what the idle_schedule / emit / yield / submit / deliver shares and the pack and upload spans cut by phase. _launches.py has the split; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.share(ctx, "host_turn")
