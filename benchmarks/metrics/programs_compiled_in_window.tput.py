"""Launches whose call compiled a program or loaded one from the compile cache (JAX recorded a backend compile across it, on its thread), of those the window's StepStats carry: 0 in a sound run, as run.py's own count. No trace is read. 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.compiled_in_window(ctx)
