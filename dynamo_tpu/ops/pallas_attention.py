"""Pallas TPU kernel: ragged paged decode attention.

Drop-in replacement for ``ops.attention.paged_decode_attention`` on the decode
hot path. The pure-JAX formulation gathers every sequence's full (padded) page
table out of HBM each step; this kernel instead walks each sequence's *actual*
pages with explicit HBM->VMEM DMAs, double-buffered so page fetch overlaps the
flash-attention compute. HBM traffic becomes proportional to the ragged sum of
true context lengths rather than B * max_blocks.

This is the TPU analog of what the reference delegates to vLLM/FlashInfer
paged-attention CUDA kernels (engine-internal; see SURVEY.md §2.5) — written
from scratch against the paged layout ``[num_blocks, block_size, kv_heads,
head_dim]`` shared with ops/attention.py and the KVBM transfer plane.

Grid: one program per sequence. Scalar-prefetched block tables + sequence
lengths (SMEM) drive the page DMAs; online-softmax (flash) accumulation over
chunks of pages keeps VMEM usage constant in context length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import shard_map
from .quant import QuantizedKV, is_quantized

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch (SMEM)
    tables_ref,     # [B * max_blocks] int32 flattened block tables
    lens_ref,       # [B] int32 context lengths (incl. current token)
    # inputs
    q_ref,          # VMEM [1, h, d] this sequence's query
    k_hbm,          # ANY/HBM [num_blocks, bs, kvh, d] (model dtype or int8)
    v_hbm,          # ANY/HBM [num_blocks, bs, kvh, d]
    # quantized=True only: ks_hbm/vs_hbm ANY/HBM [num_blocks, kvh] f32 scales
    # outputs
    # o_ref         VMEM [1, h, d]
    # scratch
    # k_buf/v_buf   VMEM [2, CP, bs, kvh, d] double-buffered page chunks
    # quantized=True only: ks_buf/vs_buf VMEM [2, CP, kvh] f32 scale rows
    # sem           DMA sems [2, 2, CP] (k/v, slot, page)
    # quantized=True only: ssem DMA sems [2, 2, CP] for the scale rows
    *rest,
    max_blocks: int,
    chunk_pages: int,
    quantized: bool,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
         ssem) = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssem = None
    b = pl.program_id(0)
    bs, kvh, d = k_hbm.shape[1], k_hbm.shape[2], k_hbm.shape[3]
    h = q_ref.shape[1]
    g = h // kvh
    CP = chunk_pages
    T = CP * bs

    seq_len = lens_ref[b]
    num_pages = pl.cdiv(seq_len, bs)
    num_chunks = pl.cdiv(num_pages, CP)

    def page_dma(kind, c, j, slot):
        """DMA descriptor for page j of chunk c into buffer slot."""
        idx = tables_ref[b * max_blocks + c * CP + j]
        src = k_hbm if kind == 0 else v_hbm
        dst = k_buf if kind == 0 else v_buf
        return pltpu.make_async_copy(
            src.at[idx], dst.at[slot, j], sem.at[kind, slot, j]
        )

    def scale_dma(kind, c, j, slot):
        """Scale-row DMA for page j: rides the same prefetched table index
        the page DMA uses — [kvh] f32 per page, ~1000x smaller than the
        payload it describes. NOTE (hardware): this slice's minor dim is
        kvh, not 128-aligned; CPU tier-1 only exercises interpret mode, so
        the first real-TPU int8 run must confirm Mosaic accepts the copy
        (fallback if not: use_pallas=False or pad scales to [nb, kvh, 128]
        sublane-major)."""
        idx = tables_ref[b * max_blocks + c * CP + j]
        src = ks_hbm if kind == 0 else vs_hbm
        dst = ks_buf if kind == 0 else vs_buf
        return pltpu.make_async_copy(
            src.at[idx], dst.at[slot, j], ssem.at[kind, slot, j]
        )

    def start_chunk(c, slot):
        for j in range(CP):  # static unroll; guard ragged tail
            @pl.when(c * CP + j < num_pages)
            def _():
                page_dma(0, c, j, slot).start()
                page_dma(1, c, j, slot).start()
                if quantized:
                    scale_dma(0, c, j, slot).start()
                    scale_dma(1, c, j, slot).start()

    def wait_chunk(c, slot):
        for j in range(CP):
            @pl.when(c * CP + j < num_pages)
            def _():
                page_dma(0, c, j, slot).wait()
                page_dma(1, c, j, slot).wait()
                if quantized:
                    scale_dma(0, c, j, slot).wait()
                    scale_dma(1, c, j, slot).wait()

    start_chunk(0, 0)

    scale = 1.0 / (d ** 0.5)
    qf = q_ref[0].astype(jnp.float32) * scale  # [h, d]

    def body(c, carry):
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            start_chunk(c + 1, jax.lax.rem(c + 1, 2))

        wait_chunk(c, slot)

        if quantized:
            # dequantize in-register: int8 page chunks -> f32 scaled by the
            # per-(page, kv-head) rows that just DMA'd in alongside them.
            # HBM traffic for the K/V bytes themselves is halved vs bf16.
            k = (
                k_buf[slot].astype(jnp.float32)
                * ks_buf[slot][:, None, :, None]
            ).reshape(T, kvh, d)
            v = (
                v_buf[slot].astype(jnp.float32)
                * vs_buf[slot][:, None, :, None]
            ).reshape(T, kvh, d)
        else:
            k = k_buf[slot].reshape(T, kvh, d).astype(jnp.float32)
            v = v_buf[slot].reshape(T, kvh, d).astype(jnp.float32)
        # rows past seq_len were never DMA'd (garbage / NaN): scores are
        # masked below, but V must be zeroed too — 0-weight * NaN = NaN in
        # the PV matmul otherwise
        row_pos = c * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1, 1), 0)
        v = jnp.where(row_pos < seq_len, v, 0.0)

        # scores [h, T]: per-kv-head MXU matmuls (GQA grouping: q heads
        # [i*g, (i+1)*g) attend kv head i, matching attention._gqa_scores)
        parts = []
        for i in range(kvh):
            s_i = jax.lax.dot_general(
                qf[i * g:(i + 1) * g], k[:, i, :],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [g, T]
            parts.append(s_i)
        s = jnp.concatenate(parts, axis=0) if kvh > 1 else parts[0]

        key_pos = c * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        s = jnp.where(key_pos < seq_len, s, NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)            # [h, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # [h, T]
        alpha = jnp.exp(m_prev - m_new)                       # [h, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        outs = []
        for i in range(kvh):
            o_i = jax.lax.dot_general(
                p[i * g:(i + 1) * g], v[:, i, :],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [g, d]
            outs.append(o_i)
        pv = jnp.concatenate(outs, axis=0) if kvh > 1 else outs[0]
        acc_new = alpha * acc_prev + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    a0 = jnp.zeros((h, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, a0))

    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk_tokens", "interpret")
)
def paged_decode_attention(
    q: jax.Array,             # [B, h, d]
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32
    seq_lens: jax.Array,      # [B] int32
    *,
    chunk_tokens: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Ragged paged decode attention (Pallas). Same semantics as
    ``ops.attention.paged_decode_attention``. ``k_cache``/``v_cache`` may be
    ``QuantizedKV`` (int8 payload + per-block scales): the kernel DMAs the
    int8 pages plus their scale rows and dequantizes in-register, so the
    per-page HBM bytes halve vs bf16."""
    B, h, d = q.shape
    _, bs, kvh, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    chunk_pages = max(1, chunk_tokens // bs)
    quantized = is_quantized(k_cache)

    kernel = functools.partial(
        _decode_kernel, max_blocks=max_blocks, chunk_pages=chunk_pages,
        quantized=quantized,
    )
    cache_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((2, chunk_pages, bs, kvh, d), k_cache.dtype),
        pltpu.VMEM((2, chunk_pages, bs, kvh, d), v_cache.dtype),
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
    scratch.append(pltpu.SemaphoreType.DMA((2, 2, chunk_pages)))
    if quantized:
        scratch.append(pltpu.SemaphoreType.DMA((2, 2, chunk_pages)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, h, d), lambda b, *_: (b, 0, 0))]
        + cache_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch,
    )
    cache_args = (
        (k_cache.data, v_cache.data, k_cache.scale, v_cache.scale)
        if quantized else (k_cache, v_cache)
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        block_tables.reshape(-1).astype(jnp.int32),
        seq_lens.astype(jnp.int32),
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
