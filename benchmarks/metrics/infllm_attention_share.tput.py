"""Share of the device's busy time in the traced sub-window spent in the
launches named ``infllm_decode_attention``: the decode rows' attention over
the pages they chose. The selection before it (pooled-key scores, the top-k)
is XLA's, inside ``fusion`` and ``sort``, and no reader tells it apart yet
(PERF.md section 7)."""
from benchmarks.metrics import _sala


def read(ctx):
    return _sala.kernel_share(ctx, _sala.ATTENTION_KERNEL)
