"""Summed device time of the whole chunk-carrying executions (prefill, mixed_step) of the sub-window over the summed key (the chunk's bucket, in tokens) of their launches: a mean weighted by tokens, which does not jump where the median of prefill_chunk_ms sits on the edge of two buckets. _launches.py; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.chunk_ms_per_token(ctx)
