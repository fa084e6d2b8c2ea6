"""What changes every step, for a synchronous step program, in ONE int32 buffer.

``prefill``, ``decode`` and ``mixed_step`` (engine ``_build_programs``: one
set of programs, whatever runs their bodies) take what the host builds anew
for each dispatch as a single argument: ``pack`` lays it out here on the host, ``unpack``
slices it inside the program, where a slice of an argument costs nothing.
On a TPU v5e every host value handed to a jitted call is a transfer of its
own at about 0.15 ms (0.25 ms through ``jnp.asarray``), so thirteen values
a step cost what one 40 ms step could not hide (PERF.md section 6, PR 29).

Layout, the same for all three programs (a program reads what it needs; the
rest stays zero): ``len(ROWS)`` rows of ``batch`` values, the ``SCALARS``,
then one sequence's block-table row. Booleans travel as 0 / 1. The length
depends on the engine's batch and ``max_blocks_per_seq`` alone, so a
program's shapes stay one set per bucket. The buffer is fresh each step: a
snapshot by construction, whatever the loop writes into its slot arrays
after the dispatch.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

# [batch] int32 each: the decode half's per-slot values of ONE step
# (engine _decode_dispatch_arrays), the host's guided-decoding FSM states,
# and which rows of a mixed step take their token from the device carry of
# the mixed step launched before it (0 / 1; ``tokens`` is not read there:
# the host has not seen that token yet)
ROWS = (
    "tokens", "positions", "seq_lens", "write_blocks", "write_offsets",
    "steps", "g_state", "carried",
)
# the chunk half's scalars (prefill's conventions) and the two top-logprob
# switches; FLAGS are read back as booleans
SCALARS = (
    "total_len", "chunk_start", "slot", "is_final", "c_lp_need", "lp_need",
    "c_g_state",
)
FLAGS = ("is_final", "c_lp_need", "lp_need")


def pack(batch: int, blocks: int, table_row=None, **values) -> np.ndarray:
    """Host side: a fresh buffer holding ``values`` (names from ROWS and
    SCALARS) and the chunk's block-table row; what is not given is zero."""
    base = len(ROWS) * batch
    buf = np.zeros(base + len(SCALARS) + blocks, np.int32)
    for name, value in values.items():
        if name in ROWS:
            k = ROWS.index(name)
            buf[k * batch : (k + 1) * batch] = value
        else:
            buf[base + SCALARS.index(name)] = value
    if table_row is not None:
        buf[base + len(SCALARS) :] = table_row
    return buf


def unpack(buf, batch: int) -> SimpleNamespace:
    """Program side: the named values of ``pack`` as slices of ``buf``
    (rows [batch], scalars 0-d, flags boolean, ``table_row`` [blocks])."""
    base = len(ROWS) * batch
    out = {name: buf[k * batch : (k + 1) * batch] for k, name in enumerate(ROWS)}
    for k, name in enumerate(SCALARS):
        out[name] = buf[base + k] != 0 if name in FLAGS else buf[base + k]
    return SimpleNamespace(table_row=buf[base + len(SCALARS) :], **out)
