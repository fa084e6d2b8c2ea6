"""What the readers of dots3-note-prev share: the launches of its two kinds
of attention and the steps that carry the program's counters
(``StepStats.winlat_keys_read``, ``.winlat_rows``, ``.winlat_chunk_tokens``
beside ``.dsa_*`` on a step's readback; PERF.md section 3). A program
without the counters gives none, and the readers return ``None``."""

from typing import List, Tuple

WINDOWED = r"windowed_latent_attention"
# the launches of both kinds' attention: the index keys' copy and the
# selected keys' products in a full layer, the window's in a sliding one. The
# index SCORES and the top-k between them are XLA's (``fusion``, ``sort``
# under the scopes ``dots3_index`` / ``dots3_select``): no reader tells them
# from the other fusions yet (PERF.md section 7)
ATTENTION = r"paged_index_keys|sparse_latent_attention|windowed_latent_attention"


def counted(steps) -> List[Tuple[float, object]]:
    """The steps that carry the sliding layers' counters."""
    return [(t, s) for t, s in steps if getattr(s, "winlat_rows", None)]


def prefilled_in_subwindow(ctx):
    """(new tokens, cached tokens) of the requests whose prompt was prefilled
    inside the traced sub-window: whole or not at all."""
    lo, hi = ctx.trace_host
    for r in ctx.requests_all:
        if r["cached_tokens"] is None or r["t_first"] is None:
            continue
        if lo <= r["t_ref"] and r["t_first"] <= hi:
            yield r["prompt_tokens"] - r["cached_tokens"], r["cached_tokens"]
