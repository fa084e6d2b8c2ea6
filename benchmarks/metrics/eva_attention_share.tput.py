"""Share of the device's busy time in the traced sub-window spent in the
launches that serve the family's attention (``eva_decode_attention`` for
decode rows, ``ragged_paged_attention`` for a prefill chunk and a mixed step):
whether the ring and the summaries are most of the work of a step."""
from benchmarks.metrics import _eva


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not ctx.trace.op_seconds(_eva.KERNEL):
        return None
    return 100.0 * ctx.trace.op_seconds(_eva.ATTENTION) / ctx.trace.busy_s
