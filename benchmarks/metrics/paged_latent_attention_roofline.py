"""Roofline share of latent attention over every causal key (every layer's
launch named ``paged_latent_attention``: decode horizons, lone chunks and
mixed steps), in the traced sub-window.

Needed (``benchmarks/costs_mla.py``), by kind of launch, each kind the larger
of its bytes and its FLOP:

- decode rows (a decode horizon's launches, and the one-token rows of a
  mixed step): bound by BYTES. A row reads one latent row a key of its
  context, once a layer and step: the program's own count
  (``StepStats.mla_keys_attended``: real decode rows x layers x context) over
  the steps that ended inside the sub-window; a horizon that straddles an
  edge is counted whole or not at all.
- a chunk (lone, or row 0 of a mixed step): bound by FLOP from 7 queries on.
  Its causal (query, key) pairs come from the requests whose prompt was
  prefilled inside the sub-window (the uncached part, query by query at its
  position, once a layer); its context is read once a layer.

The two are added (a mixed step's launch does both, one after the other),
over the summed device time of the launches.
"""
from benchmarks import costs_mla
from benchmarks.metrics import _mla

KERNEL = r"paged_latent_attention"


def chunk_work(ctx):
    """(keys read, causal pairs) of the chunks prefilled inside the
    sub-window, one layer: a prompt's uncached part at its positions."""
    lo, hi = ctx.trace_host
    keys = pairs = 0.0
    for r in ctx.requests_all:
        if r["cached_tokens"] is None or r["t_first"] is None:
            continue
        if not (lo <= r["t_ref"] and r["t_first"] <= hi):
            continue
        n, c = r["prompt_tokens"], r["cached_tokens"]
        keys += n
        pairs += n * (n + 1) / 2 - c * (c + 1) / 2
    return keys, pairs


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(KERNEL)
    lo, hi = ctx.trace_host
    steps = [s for t, s in _mla.counted(ctx.steps_all) if lo <= t < hi]
    if seconds <= 0 or not steps:
        return None
    decode_keys = sum(s.mla_keys_attended for s in steps)
    keys, pairs = chunk_work(ctx)
    L = ctx.cfg["num_hidden_layers"]
    need = costs_mla.launch_least_s(ctx.cfg, decode_keys, decode_keys, ctx.peaks) \
        + L * costs_mla.launch_least_s(ctx.cfg, keys, pairs, ctx.peaks)
    return 100.0 * need / seconds
