"""Pallas TPU kernel: ragged paged decode attention.

Drop-in replacement for ``ops.attention.paged_decode_attention`` on the decode
hot path. The pure-JAX formulation gathers every sequence's full (padded) page
table out of HBM each step; this kernel instead walks each sequence's *actual*
pages with explicit HBM->VMEM DMAs, double-buffered so page fetch overlaps the
flash-attention compute. HBM traffic becomes proportional to the ragged sum of
true context lengths rather than B * max_blocks, and the kernel runs at the
speed of those page reads (PERF.md section 5).

This is the TPU analog of what the reference delegates to vLLM/FlashInfer
paged-attention CUDA kernels (engine-internal; see SURVEY.md §2.5) — written
from scratch against the paged layout ``[num_blocks, block_size, kv_heads,
head_dim]`` shared with ops/attention.py and the KVBM transfer plane.

Grid: ONE program; the rows of the batch are a loop inside it, so that reads
stay in flight from one row to the next. Scalar-prefetched block tables +
sequence lengths (SMEM) drive the page DMAs.

Chunking: a row's pages are walked in chunks of ``chunk_pages`` pages, as many
as ``pallas_paged.VMEM_CHUNK_BYTES`` holds in two slots of K and V (512 tokens
at 8 kv heads x 128 in bf16) and no more than a row can have (the rule, the
page copies and the own-head bias live in ops/pallas_paged.py, shared with the
ragged kernel). The caches are handed
over viewed as ``[num_blocks, block_size * kv_heads, head_dim]`` (the same
bytes), so a chunk lands in VMEM as one dense ``[tokens * kv_heads, head_dim]``
matrix: chunk row ``r`` is token ``r // kv_heads`` of kv head ``r % kv_heads``.

Compute: ALL kv heads go through one product a chunk. ``q[h, d] . K^T`` gives
``[h, tokens * kv_heads]``; query head ``i`` keeps the columns of its own kv
head (``col % kv_heads == i // g``, a mask built once a call and added as a
bias) and the rest leave the softmax as exact zeros, so ``p . V`` over the
whole chunk is the per-head sum. Q and K meet the matrix unit in the dtype they
arrive in (bf16 x bf16 products are exact in the f32 accumulator) and
``1/sqrt(d)`` is applied to the f32 scores; the softmax state and ``p`` are f32.
Only a row's LAST chunk can hold tokens past ``seq_len``: it alone masks the
scores against the length and zeroes those V rows (never-read or stale VMEM
may hold NaN, and 0 * NaN = NaN); full chunks skip both.

In flight: while chunk ``c`` of a row is computed, chunk ``c + 1`` is being
read into the other slot; while a row's last chunk is computed, the first
chunk of the next non-empty row is. Rows with ``seq_len == 0`` (padding) read
nothing, wait for nothing and return zeros.

How a chunk is read is ``pallas_paged.PageReader``'s rule, chosen a chunk
from what the tables hold: a WHOLE chunk whose table entries are consecutive
block ids (a prompt admitted in one go into a pool that hands out low ids
first; ``chunk_runs`` of the tables, one compare in the launch's XLA wrapper,
a third scalar-prefetch operand) is ONE descriptor an array, K and V; any
other whole chunk is started page by page; both are waited for once an array
(a DMA semaphore counts bytes). A row's tail chunk starts and waits page by
page. The scalar unit issues descriptors in the products' instruction stream,
12 ns each: at 32 KiB pages a chunk was 64 of them and at 16 KiB 128, on top
of the products wherever those do not hide under the chunk's bytes (128 query
heads; PERF.md section 6, PR 50 has the table). The output is bitwise the
same whichever way a chunk came in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import shard_map
from . import pallas_paged as paged
from .pallas_paged import NEG_INF
from .quant import QuantizedKV, is_quantized


def _decode_kernel(
    # scalar prefetch (SMEM)
    tables_ref,     # [B * max_blocks] int32 flattened block tables
    lens_ref,       # [B] int32 context lengths (incl. current token)
    runs_ref,       # [B * (max_blocks // CP)] int32: which whole chunks of a
    #                 table are consecutive block ids (pallas_paged.chunk_runs)
    # inputs
    q_ref,          # VMEM [B, h, d] every sequence's query
    k_hbm,          # ANY/HBM [num_blocks, bs * kvh, d] (model dtype or int8)
    v_hbm,          # ANY/HBM [num_blocks, bs * kvh, d]
    # quantized=True only: ks_hbm/vs_hbm ANY/HBM [num_blocks, kvh] f32 scales
    # outputs
    # o_ref         VMEM [B, h, d]
    # scratch
    # k_buf/v_buf   VMEM [2, CP, bs * kvh, d] double-buffered page chunks
    # quantized=True only: ks_buf/vs_buf VMEM [2, CP, kvh] f32 scale rows
    # bias_ref      VMEM [h, CP * bs * kvh] f32: 0 on a query head's own kv
    #               head's columns, NEG_INF elsewhere
    # sem           DMA sems [2, 2] (k/v, slot)
    # quantized=True only: ssem DMA sems [2, 2] for the scale rows
    *rest,
    max_blocks: int,
    chunk_pages: int,
    kvh: int,
    quantized: bool,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, bias_ref, sem,
         ssem) = rest
    else:
        o_ref, k_buf, v_buf, bias_ref, sem = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssem = None
    B, h, d = q_ref.shape
    R = k_hbm.shape[1]      # rows of a page: (token, kv head) pairs
    bs = R // kvh
    g = h // kvh
    CP = chunk_pages
    N = CP * R
    T = CP * bs

    pages = paged.PageReader(
        tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
        (ks_hbm, vs_hbm, ks_buf, vs_buf, ssem) if quantized else None,
        runs_ref=runs_ref, chunk_pages=CP,
    )

    def pages_in_chunk(row, c):
        return jnp.minimum(CP, pl.cdiv(lens_ref[row], bs) - c * CP)

    def start_chunk(row, c, slot):
        pages.start(
            row * max_blocks + c * CP, pages_in_chunk(row, c), slot,
            row * (max_blocks // CP) + c,
        )

    def wait_chunk(row, c, slot):
        pages.wait(pages_in_chunk(row, c), slot)

    def start_next_row(row, slot):
        """Start chunk 0 of the first non-empty row after ``row``, if any."""
        nxt = jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < B, lens_ref[jnp.minimum(r, B - 1)] == 0),
            lambda r: r + 1,
            row + 1,
        )

        @pl.when(nxt < B)
        def _():
            start_chunk(nxt, 0, slot)

    bias_ref[...] = paged.own_head_bias(h, g, kvh, N)
    scale = 1.0 / (d ** 0.5)
    start_next_row(-1, 0)

    def row_body(b, slot0):
        seq_len = lens_ref[b]
        num_chunks = pl.cdiv(pl.cdiv(seq_len, bs), CP)
        q = q_ref[b]

        def chunk(c, carry, *, tail):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(slot0 + c, 2)
            if tail:
                start_next_row(b, 1 - slot)
            else:
                start_chunk(b, c + 1, 1 - slot)
            wait_chunk(b, c, slot)

            # chunk rows below `limit` are inside the context
            limit = (seq_len - c * T) * kvh
            k = k_buf[slot].reshape(N, d)
            v = v_buf[slot].reshape(N, d)
            if quantized:
                # dequantize in-register: int8 page chunks -> f32 scaled by
                # the per-(page, kv-head) rows that DMA'd in alongside them.
                # HBM traffic for the K/V bytes themselves is halved vs bf16.
                def dequant(x, s):
                    x = x.astype(jnp.float32).reshape(CP, bs, kvh, d)
                    return (x * s[:, None, :, None]).reshape(N, d)

                k, v = dequant(k, ks_buf[slot]), dequant(v, vs_buf[slot])
            v = v.astype(jnp.float32)
            if tail:
                # rows past seq_len were never DMA'd (stale / NaN): scores
                # are masked below, but V must be zeroed too — 0-weight *
                # NaN = NaN in the PV matmul otherwise
                rows = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
                v = jnp.where(rows < limit, v, 0.0)
            qk = q
            if k.dtype != q.dtype:
                qk, k = q.astype(jnp.float32), k.astype(jnp.float32)

            # scores [h, N] of every query head against every kv head's keys
            # in ONE product; the bias keeps a query head's own kv head (GQA
            # grouping: q heads [i*g, (i+1)*g) attend kv head i, matching
            # attention._gqa_scores)
            s = jax.lax.dot_general(
                qk, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + bias_ref[...]
            if tail:
                key_row = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
                s = jnp.where(key_row < limit, s, NEG_INF)

            m_cur = jnp.max(s, axis=-1, keepdims=True)            # [h, 1]
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                                # [h, N]
            alpha = jnp.exp(m_prev - m_new)                       # [h, 1]
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [h, d]
            return m_new, l_new, alpha * acc_prev + pv

        @pl.when(seq_len == 0)
        def _():
            o_ref[b] = jnp.zeros((h, d), o_ref.dtype)

        @pl.when(seq_len > 0)
        def _():
            m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((h, 1), jnp.float32)
            a0 = jnp.zeros((h, d), jnp.float32)
            carry = jax.lax.fori_loop(
                0, num_chunks - 1, functools.partial(chunk, tail=False),
                (m0, l0, a0),
            )
            _, l, acc = chunk(num_chunks - 1, carry, tail=True)
            o_ref[b] = (acc / l).astype(o_ref.dtype)

        return jax.lax.rem(slot0 + num_chunks, 2)

    jax.lax.fori_loop(0, B, row_body, 0)


KERNEL_NAME = "paged_decode_attention"


@functools.partial(
    jax.jit, static_argnames=("chunk_tokens", "interpret", "name")
)
def paged_decode_attention(
    q: jax.Array,             # [B, h, d]
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32
    seq_lens: jax.Array,      # [B] int32
    *,
    chunk_tokens: Optional[int] = None,
    interpret: bool = False,
    name: str = KERNEL_NAME,
) -> jax.Array:
    """Ragged paged decode attention (Pallas). Same semantics as
    ``ops.attention.paged_decode_attention``; a row with ``seq_len == 0``
    returns zeros. ``k_cache``/``v_cache`` may be ``QuantizedKV`` (int8
    payload + per-block scales): the kernel DMAs the int8 pages plus their
    scale rows and dequantizes in-register, so the per-page HBM bytes halve
    vs bf16. ``chunk_tokens`` overrides the derived chunk size (tests: a
    chunk loop over small contexts); no call site of the program sets it.
    ``name``: the launch's name on the device trace, for a family whose rows
    are paged sequences of another make (ops/pallas_eva.py)."""
    B, h, d = q.shape
    nb, bs, kvh, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    quantized = is_quantized(k_cache)
    pages = k_cache.data if quantized else k_cache
    if chunk_tokens is None:
        chunk_pages = paged.chunk_pages(bs, kvh, d, pages.dtype, max_blocks)
    else:
        chunk_pages = max(1, min(chunk_tokens // bs, max_blocks))

    kernel = functools.partial(
        _decode_kernel, max_blocks=max_blocks, chunk_pages=chunk_pages,
        kvh=kvh, quantized=quantized,
    )
    cache_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((2, chunk_pages, bs * kvh, d), pages.dtype),
        pltpu.VMEM((2, chunk_pages, bs * kvh, d), pages.dtype),
    ]
    if quantized:
        cache_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # k scales [num_blocks, kvh]
            pl.BlockSpec(memory_space=pl.ANY),  # v scales
        ]
        scratch += [
            pltpu.VMEM((2, chunk_pages, kvh), jnp.float32),
            pltpu.VMEM((2, chunk_pages, kvh), jnp.float32),
        ]
    scratch.append(pltpu.VMEM((h, chunk_pages * bs * kvh), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((2, 2)))
    if quantized:
        scratch.append(pltpu.SemaphoreType.DMA((2, 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec((B, h, d), lambda i, *_: (0, 0, 0))]
        + cache_specs,
        out_specs=pl.BlockSpec((B, h, d), lambda i, *_: (0, 0, 0)),
        scratch_shapes=scratch,
    )

    def rows(cache):  # [nb, bs, kvh, d] -> [nb, bs * kvh, d]: the same bytes
        return cache.reshape(nb, bs * kvh, d)

    block_tables = block_tables.astype(jnp.int32)
    cache_args = (
        (rows(k_cache.data), rows(v_cache.data), k_cache.scale, v_cache.scale)
        if quantized else (rows(k_cache), rows(v_cache))
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, d), q.dtype),
        interpret=interpret,
        name=name,
    )(
        block_tables.reshape(-1),
        seq_lens.astype(jnp.int32),
        paged.chunk_runs(block_tables, chunk_pages).reshape(-1).astype(
            jnp.int32),
        q,
        *cache_args,
    )


def sharded_paged_decode_attention(
    mesh: Mesh,
    tp_axis: str,
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    **kw,
) -> jax.Array:
    """TP-sharded wrapper: attention is head-wise independent, so each TP
    shard runs the kernel on its own heads (q sharded on h, caches on kvh —
    parallel/mesh.kv_cache_spec). Uses shard_map because XLA's GSPMD cannot
    partition a custom call on its own."""
    if mesh.shape[tp_axis] == 1:
        return paged_decode_attention(
            q, k_cache, v_cache, block_tables, seq_lens, **kw
        )
    cache_spec = P(None, None, tp_axis, None)
    if is_quantized(k_cache):
        # spec tree mirrors the QuantizedKV pytree: payload shards on
        # kv_heads like the float cache, scale rows on their kv-head dim
        cache_spec = QuantizedKV(cache_spec, P(None, tp_axis))
    fn = shard_map(
        functools.partial(paged_decode_attention, **kw),
        mesh=mesh,
        in_specs=(
            P(None, tp_axis, None),
            cache_spec,
            cache_spec,
            P(None, None),
            P(None),
        ),
        out_specs=P(None, tp_axis, None),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, block_tables, seq_lens)
