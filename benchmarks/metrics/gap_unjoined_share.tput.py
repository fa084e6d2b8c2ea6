"""Share of the traced window in which device 0 sat idle before an execution that found no launch record: the check on the launch ledger, as idle_unattributed_share is on the spans. _launches.py has the join; 0.0 from a program without the ledger."""
from benchmarks.metrics import _launches


def read(ctx):
    return _launches.share(ctx, "unjoined")
