"""Pallas TPU launch: EVA decode attention (models/evabyte.py).

One query a row: an online softmax over the row's RING pages up to its
position in the open window and over one learned summary a chunk of every
closed window before it, each needed byte read once.

The ring and the summary blocks live in the same pool, a summary block as
``window / chunk / page`` whole pages (ops/attention.py has the layout), so
a row is ONE paged sequence: the pages of its closed windows' summaries, then
its ring's, ``w * chunks_per_window + (p mod window) + 1`` keys long, and the
one softmax over both sets is the decode kernel's own walk over it
(ops/pallas_attention.py: chunks of pages double-buffered from row to row, all
heads through one masked product a chunk, a row's last chunk alone masked
against its length). What is this module's is the rows' make
(``attention.eva_paged_view``: which pages, in which order, how long) and the
launch's NAME on the device trace, so that the family's decode attention is
told from a dense family's (``eva_decode_attention_roofline``,
benchmarks/costs_eva.py counts its bytes). The order of the two sets is the
kernel's to choose, summaries first: only a sequence's last page may be
partial, and that is the ring's.

At the published widths (32 heads x 128, multi-head) a chunk is 8 pages, 128
keys, 2 MiB of K and V; the masked product scores a query head against every
head's keys of the chunk and keeps its own (1 column in 32).
"""

from __future__ import annotations

import jax

from . import attention as att
from . import pallas_attention as pa

KERNEL_NAME = "eva_decode_attention"


def eva_decode_attention(
    q: jax.Array,             # [B, h, d] one query a row
    k_cache: jax.Array,       # [pages, page, h, d]: ring pages and summaries
    v_cache: jax.Array,
    tables: jax.Array,        # [B, ring pages + windows] int32
    seq_lens: jax.Array,      # [B] int32 context with the fed token; 0 = empty
    eva: att.EvaQuery,        # the window's geometry (its vectors are not read)
    summary_base: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Same semantics, and arguments, as
    ``ops.attention.eva_paged_decode_attention`` (its pure-JAX twin); a row
    with ``seq_len == 0`` returns zeros."""
    view, lens = att.eva_paged_view(
        tables, seq_lens, eva, k_cache.shape[1], summary_base
    )
    return pa.paged_decode_attention(
        q, k_cache, v_cache, view, lens, interpret=interpret, name=KERNEL_NAME,
    )
