"""What the two paged-attention kernels share (ops/pallas_attention.py, the
decode kernel; ops/pallas_unified.py, the ragged one): how many pages make a
chunk, how a chunk's page copies are issued and waited for, and the bias that
lets ONE product serve every kv head of a dense chunk.

Both kernels see the caches as ``[num_blocks, block_size * kv_heads,
head_dim]`` (the same bytes as the paged layout), so a chunk of ``chunk_pages``
pages lands in a ``[2, chunk_pages, block_size * kv_heads, head_dim]`` VMEM
buffer (two slots) as one dense matrix: chunk row ``r`` is token ``r //
kv_heads`` of kv head ``r % kv_heads``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
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


class PageReader:
    """Issue and wait for a chunk's page copies. One DMA semaphore a slot and
    kind (``sem[kind, slot]``): every page copy of a chunk signals it, and it
    is waited once a page. With ``scales`` (int8 caches: ``(ks_hbm, vs_hbm,
    ks_buf, vs_buf, ssem)``) a page's ``[kvh]`` f32 scale rows ride the same
    table index. NOTE (hardware): that slice's minor dim is kvh, not
    128-aligned; Mosaic refuses the copy (tests/test_tpu_compile.py pins it)
    and the engine refuses int8 with the Pallas kernels on the TPU backend,
    so the scale copies run interpreted only."""

    def __init__(self, tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
                 scales=None):
        self.tables_ref = tables_ref
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

    def start(self, base, num_pages, slot):
        """Start the copies of pages ``tables_ref[base + j]``, ``j <
        num_pages``, into places ``0..num_pages-1`` of ``slot``."""
        def issue(j, carry):
            for copy in self.copies(slot, self.tables_ref[base + j], j):
                copy.start()
            return carry

        jax.lax.fori_loop(0, num_pages, issue, 0)

    def wait(self, num_pages, slot):
        def one(j, carry):
            # the descriptor only says how many bytes one page signals
            for copy in self.copies(slot, 0, 0):
                copy.wait()
            return carry

        jax.lax.fori_loop(0, num_pages, one, 0)
