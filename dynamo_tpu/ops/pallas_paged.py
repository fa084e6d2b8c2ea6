"""What the paged-attention kernels share (ops/pallas_attention.py, the
decode kernel; ops/pallas_unified.py, the ragged one; ops/pallas_latent.py
and ops/pallas_sparse.py for the first two): how many pages make a chunk,
which whole chunks of a table are runs of consecutive pages, how a chunk's
copies are issued and waited for (``PageReader``: a run as one descriptor an
array, anything else page by page), and the bias that lets ONE product serve
every kv head of a dense chunk.

Both kernels see the caches as ``[num_blocks, block_size * kv_heads,
head_dim]`` (the same bytes as the paged layout), so a chunk of ``chunk_pages``
pages lands in a ``[2, chunk_pages, block_size * kv_heads, head_dim]`` VMEM
buffer (two slots) as one dense matrix: chunk row ``r`` is token ``r //
kv_heads`` of kv head ``r % kv_heads``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM for the page buffers: two slots each of K and V. 4 MiB reads 512 tokens
# a chunk at 8 kv heads x 128 in bf16; on a v5e 256 to 1024 tokens a chunk all
# run within 1% of each other (PERF.md section 6, PR 25).
VMEM_CHUNK_BYTES = 4 * 1024 * 1024


def chunk_pages(bs: int, kvh: int, d: int, dtype, max_blocks: int) -> int:
    """Pages a chunk: what ``VMEM_CHUNK_BYTES`` holds, at most a row's."""
    page_bytes = bs * kvh * d * jnp.dtype(dtype).itemsize
    return max(1, min(VMEM_CHUNK_BYTES // (4 * page_bytes), max_blocks))


def own_head_bias(
    h: int, g: int, kvh: int, n: int, tokens: int = 1
) -> jax.Array:
    """``[tokens * h, n]`` f32 over a dense chunk's ``n`` rows: 0 where the
    row is of query head ``i``'s own kv head (``row % kvh == i // g``),
    NEG_INF elsewhere; bias row ``r`` is query head ``r % h`` (of token ``r
    // h``). Added to the scores of all heads against all rows, it leaves the
    other kv heads' columns out of the softmax as exact zeros."""
    col = jax.lax.broadcasted_iota(jnp.int32, (tokens * h, n), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (tokens * h, n), 0)
    if tokens > 1:
        head = jax.lax.rem(head, h)
    return jnp.where(
        jax.lax.rem(col, kvh) == head // g, 0.0, NEG_INF
    ).astype(jnp.float32)


# pages a pass of the loop that starts, page by page, a whole chunk that is not
# a run (the scalar unit issues one descriptor after the other, in the
# products' instruction stream). The latent kernel's 8 decode rows over 25k
# keys ran 2.35 / 2.20 / 2.14 / 2.14 ms at 1 / 4 / 8 / 16, bitwise the same
# (PERF.md section 6, PR 33; launches timed alone, 0.65 ms of dispatch in
# each). What is left at 8 is the descriptors themselves: 1.516 ms against
# 1.199 where every whole chunk is a run and 1.147 with no copy at all (PR 34)
UNROLL = 8


def chunk_runs(tables: jax.Array, cp: int) -> jax.Array:
    """``[R, mb // cp]`` bool: which whole chunks of ``cp`` entries of each
    table are consecutive block ids, so that a chunk's pages lie one after
    the other in the pool. Every neighbour is compared: first and last id
    alone do not prove a run (``[5, 100, 7, 8]``)."""
    R, mb = tables.shape
    n = mb // cp
    t = tables[:, :n * cp].reshape(R, n, cp)
    return jnp.all(t[:, :, 1:] == t[:, :, :-1] + 1, axis=-1)


class PageReader:
    """Issue and wait for a chunk's page copies. One DMA semaphore a slot and
    kind (``sem[kind, slot]``): every copy of a chunk signals it, by the
    bytes it moved. With ``scales`` (int8 caches: ``(ks_hbm, vs_hbm, ks_buf,
    vs_buf, ssem)``) a page's ``[kvh]`` f32 scale rows ride the same table
    index. NOTE (hardware): that slice's minor dim is kvh, not 128-aligned;
    Mosaic refuses the copy (tests/test_tpu_compile.py pins it) and the
    engine refuses int8 with the Pallas kernels on the TPU backend, so the
    scale copies run interpreted only.

    How a chunk is read is chosen a chunk, from what the tables hold. Without
    ``runs_ref`` every chunk starts and waits page by page (the ragged
    kernel: a windowed row's chunks start where its window does, not at the
    table's aligned chunks). With it (``chunk_runs`` of the launch's tables
    at ``chunk_pages``, scalar-prefetched) a WHOLE chunk, ``chunk_pages``
    pages, whose table entries are consecutive block ids is ONE descriptor an
    array; every other whole chunk is started page by page, ``UNROLL`` pages
    a pass; either way it is waited for ONCE an array, with a descriptor of
    the buffer's size. A tail chunk starts and waits page by page. The same
    bytes land in the same places whichever way a chunk came in."""

    def __init__(self, tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
                 scales=None, runs_ref=None, chunk_pages=None):
        self.tables_ref = tables_ref
        self.runs_ref, self.cp = runs_ref, chunk_pages
        self.pairs = [(k_hbm, k_buf, sem.at[0]), (v_hbm, v_buf, sem.at[1])]
        if scales is not None:
            ks_hbm, vs_hbm, ks_buf, vs_buf, ssem = scales
            self.pairs += [
                (ks_hbm, ks_buf, ssem.at[0]), (vs_hbm, vs_buf, ssem.at[1]),
            ]

    def copies(self, slot, idx, j):
        """Descriptors of page ``idx`` into place ``j`` of ``slot``."""
        return [
            pltpu.make_async_copy(src.at[idx], dst.at[slot, j], sem.at[slot])
            for src, dst, sem in self.pairs
        ]

    def run_copies(self, slot, idx):
        """Descriptors of the ``chunk_pages`` pages from ``idx`` on, which
        lie one after the other in the pool, into the whole of ``slot``."""
        return [
            pltpu.make_async_copy(
                src.at[pl.ds(idx, self.cp)], dst.at[slot], sem.at[slot])
            for src, dst, sem in self.pairs
        ]

    def start(self, base, num_pages, slot, chunk=None):
        """Start the copies of pages ``tables_ref[base + j]``, ``j <
        num_pages``, into places ``0..num_pages-1`` of ``slot``. ``chunk``:
        the chunk's place in ``runs_ref`` (read only where the chunk is
        whole: a row's tail chunk may lie past its last entry)."""
        if self.runs_ref is None:
            return self._start_pages(base, num_pages, slot)
        whole = num_pages == self.cp
        unroll = UNROLL if self.cp % UNROLL == 0 else 1

        @pl.when(whole)
        def _chunk():
            run = self.runs_ref[chunk] != 0

            @pl.when(run)
            def _run():
                for copy in self.run_copies(slot, self.tables_ref[base]):
                    copy.start()

            @pl.when(jnp.logical_not(run))
            def _pages():
                def group(g, carry):
                    for i in range(unroll):
                        j = g * unroll + i
                        for copy in self.copies(
                                slot, self.tables_ref[base + j], j):
                            copy.start()
                    return carry

                jax.lax.fori_loop(0, self.cp // unroll, group, 0)

        @pl.when(jnp.logical_not(whole))
        def _tail():
            self._start_pages(base, num_pages, slot)

    def wait(self, num_pages, slot):
        if self.runs_ref is None:
            return self._wait_pages(num_pages, slot)
        whole = num_pages == self.cp

        @pl.when(whole)
        def _chunk():
            # never started: the descriptors say how many bytes to wait for,
            # the same however the chunk was started
            for _, buf, sem in self.pairs:
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sem.at[slot]).wait()

        @pl.when(jnp.logical_not(whole))
        def _tail():
            self._wait_pages(num_pages, slot)

    def _start_pages(self, base, num_pages, slot):
        def issue(j, carry):
            for copy in self.copies(slot, self.tables_ref[base + j], j):
                copy.start()
            return carry

        jax.lax.fori_loop(0, num_pages, issue, 0)

    def _wait_pages(self, num_pages, slot):
        def one(j, carry):
            # the descriptor only says how many bytes one page signals
            for copy in self.copies(slot, 0, 0):
                copy.wait()
            return carry

        jax.lax.fori_loop(0, num_pages, one, 0)
