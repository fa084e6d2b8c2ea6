"""Pallas TPU kernel: unified ragged paged attention (prefill + decode fused).

ONE launch serves an arbitrary mix of prefill chunks and decode tokens — the
"Ragged Paged Attention" formulation (PAPERS.md) that lets the engine step
loop run true continuous batches instead of alternating a prefill-only
kernel (ops/pallas_prefill.py flash extend) with a decode-only kernel
(ops/pallas_attention.py ragged decode). Rows carry ``(query_len, seq_len)``
pairs: query tokens pack densely into one ragged buffer, each row's segment
sits at the TAIL of its own paged context, and causal masking is per row.

Beyond the base pair, rows may carry OPTIONAL per-row attributes — the
additions that let the gated model families ride the same launch:

- ``windows`` [R] int32: per-row sliding-window bound (``<= 0`` = full
  attention). Key ``j`` is visible to query ``i`` iff ``i - w < j <= i``,
  and the page-chunk loop STARTS at the first chunk the earliest query of
  the block can see — a 128-token window over a 128k context streams
  ~window keys, not the whole cache (the gpt-oss/gemma sliding layers);
- ``sinks`` [h] f32: per-head attention-sink logits (gpt-oss), folded into
  the softmax denominator by seeding each tile's online-softmax state with
  the sink as a virtual zero-value key (``m0 = sink, l0 = 1, acc0 = 0``) —
  algebraically identical to ops/attention._sink_softmax;
- ``softcap`` (static float): gemma-2 logit softcapping,
  ``cap * tanh(s / cap)`` applied post-scale, pre-mask.

A speculative-decode verify pass is just a row with ``query_len = k + 1``
(candidate tokens at the context tail) — no special case in the kernel.

Versus the split prefill path there is no gather: that path materializes
the FULL padded context (``gather_kv`` over ``max_blocks_per_seq`` pages, an
HBM->HBM copy) before the flash kernel even starts; here KV pages stream
straight from the paged cache, and only the real pages below each query
block's causal limit are ever touched. ``ops/costs.py`` turns both layouts
into byte counts; the tier-1 gate pins mixed <= split (including the
windowed and spec-verify row shapes).

Layout: paged cache ``[num_blocks, block_size, kv_heads, head_dim]``, shared
with the decode kernel and the transfer plane. A page moves as ONE whole
``[bs, kvh, d]`` DMA, as in the decode kernel: Mosaic tiles the cache's two
minor dims ``(kvh, d)`` together (bf16 packs two kv heads into one 32-bit
sublane word), so a single head cannot be sliced out of a page in HBM — the
head is selected in VMEM instead. int8 caches (ops/quant.QuantizedKV) DMA
the int8 pages PLUS their per-block ``[kvh]`` f32 scale rows on the same
scalar-prefetched table indices and dequantize in-register; that scale-row
copy is interpret-only (Mosaic refuses its unaligned minor dim), so the
engine refuses int8 + Pallas on the TPU backend at construction.

Grid: ``(Tq_pad / q_block,)`` — one program per BLOCK of ``q_block`` packed
query tokens, all heads. The q/o blocks ``[kvh, q_block * g, d]`` are
BlockSpec-pipelined and each o block is written by exactly one program
(zeros for tokens no row owns). Inside a program a loop walks the R rows
and skips those with no token in the block; for a row that has some:
double-buffered whole-page DMAs chunked ``chunk_pages`` at a time up to the
block's causal limit, a DYNAMIC inner loop over the row's ``q_seg``-token
sub-tiles inside the block with online-softmax state per (head, sub-tile)
in VMEM scratch, and a masked emit into the o block so rows that share a
sub-tile (consecutive decode tokens) keep each other's outputs. A decode
row costs one sub-tile per chunk (bandwidth-bound, each page read once); a
prefill chunk spanning several blocks re-streams its causal prefix once per
block (``q_block`` = 128 matches the flash-extend q tile, without that
path's gather or its reads past the causal limit).
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

# packed query tokens per grid program (one VMEM-resident q/o block)
Q_BLOCK = 128

KERNEL_NAME = "ragged_paged_attention"
KERNEL_NAME_WINDOWED = "ragged_paged_attention_windowed"


def default_q_seg(g: int) -> int:
    """Query tokens per inner sub-tile: small enough that a decode row
    (q_len=1) stays bandwidth-bound, and ``q_seg * g`` a multiple of 16 so
    a sub-tile's rows are whole packed sublane tiles in bf16."""
    return 8 if g % 2 == 0 else 16


def _unified_kernel(
    *args,
    max_blocks: int,
    chunk_pages: int,
    q_seg: int,
    q_block: int,
    num_rows: int,
    quantized: bool,
    has_window: bool,
    has_sinks: bool,
    softcap,
):
    # args layout (optional pieces gated by the static flags):
    #   scalar prefetch (SMEM): starts [R], qlens [R], lens [R],
    #     [windows [R]], tables [R * max_blocks]
    #   inputs: q VMEM [kvh, QB*g, d], [sinks VMEM [kvh, q_seg*g, 1]],
    #     k/v ANY/HBM [num_blocks, bs, kvh, d],
    #     [k/v scales ANY/HBM [num_blocks, kvh] f32]
    #   outputs: o VMEM [kvh, QB*g, d]
    #   scratch: k/v_buf VMEM [2, CP, bs, kvh, d], [k/v scale bufs
    #     [2, CP, kvh]], m/l/acc VMEM [kvh, QB*g, 1/1/d] f32,
    #     DMA sems [2, 2, CP] (+quant)
    it = iter(args)
    starts_ref = next(it)
    qlens_ref = next(it)
    lens_ref = next(it)
    windows_ref = next(it) if has_window else None
    tables_ref = next(it)
    q_ref = next(it)
    sinks_ref = next(it) if has_sinks else None
    k_hbm = next(it)
    v_hbm = next(it)
    ks_hbm = vs_hbm = None
    if quantized:
        ks_hbm = next(it)
        vs_hbm = next(it)
    o_ref = next(it)
    k_buf = next(it)
    v_buf = next(it)
    ks_buf = vs_buf = None
    if quantized:
        ks_buf = next(it)
        vs_buf = next(it)
    m_scr = next(it)
    l_scr = next(it)
    acc_scr = next(it)
    sem = next(it)
    ssem = next(it) if quantized else None

    bs, kvh, d = k_hbm.shape[1], k_hbm.shape[2], k_hbm.shape[3]
    g = q_ref.shape[1] // q_block
    CP = chunk_pages
    T = CP * bs
    QG = q_seg * g
    scale = 1.0 / (d ** 0.5)
    blk_lo = pl.program_id(0) * q_block

    # tokens of this block that no row owns (gaps between segments, bucket
    # padding) must read back deterministic zeros, matching the twin
    o_ref[...] = jnp.zeros_like(o_ref)

    def sub_tile(st):
        """Rows [st*QG, (st+1)*QG) of the (token, group)-flattened block."""
        return pl.ds(pl.multiple_of(st * QG, QG), QG)

    def tile_tokens(st):
        # packed token index per flattened (q, g) pair, built directly in
        # the [QG, 1] layout (iota // g keeps the lane dim fixed — see
        # pallas_prefill)
        row = jax.lax.broadcasted_iota(jnp.int32, (QG, 1), 0) // g
        return blk_lo + st * q_seg + row

    def row_body(r, carry):
        q_start = starts_ref[r]
        q_len = qlens_ref[r]
        seq_len = lens_ref[r]
        # [a, b): the row's tokens that fall inside this block
        a = jnp.maximum(q_start, blk_lo)
        b = jnp.minimum(q_start + q_len, blk_lo + q_block)

        @pl.when(jnp.logical_and(b > a, seq_len > 0))
        def _row():
            # packed token index + off = absolute position in the context
            off = seq_len - q_len - q_start
            # keys any member query can see end at the last member's limit
            kv_end = jnp.minimum(b + off, seq_len)
            num_pages = pl.cdiv(kv_end, bs)
            chunks = pl.cdiv(num_pages, CP)
            st_lo = (a - blk_lo) // q_seg
            st_hi = pl.cdiv(b - blk_lo, q_seg)
            if has_window:
                # the block's earliest member query (position a + off) sees
                # no key below a + off - w + 1: pages a sliding window
                # already aged out are never DMA'd (page-granular, like the
                # split decode path's trailing-window gather), and the chunk
                # loop starts at the first chunk holding a live page
                w = windows_ref[r]
                lo_page = jnp.where(
                    w > 0, jnp.maximum(a + off - w + 1, 0) // bs, 0
                )
                c_lo = lo_page // CP
            else:
                w = None
                lo_page = 0
                c_lo = 0

            def page_dma(kind, c, j, slot):
                """Whole-page DMA [bs, kvh, d] for page j of chunk c."""
                idx = tables_ref[r * max_blocks + c * CP + j]
                src = k_hbm if kind == 0 else v_hbm
                dst = k_buf if kind == 0 else v_buf
                return pltpu.make_async_copy(
                    src.at[idx], dst.at[slot, j], sem.at[kind, slot, j]
                )

            def scale_dma(kind, c, j, slot):
                """[kvh] f32 scale row for page j, riding the same
                prefetched table index (interpret-only: see module doc)."""
                idx = tables_ref[r * max_blocks + c * CP + j]
                src = ks_hbm if kind == 0 else vs_hbm
                dst = ks_buf if kind == 0 else vs_buf
                return pltpu.make_async_copy(
                    src.at[idx], dst.at[slot, j], ssem.at[kind, slot, j]
                )

            def page_live(c, j):
                """Page j of chunk c holds keys some member query sees."""
                live = c * CP + j < num_pages
                if has_window:
                    live = jnp.logical_and(live, c * CP + j >= lo_page)
                return live

            def start_chunk(c, slot):
                for j in range(CP):  # static unroll; guard ragged tail + window
                    @pl.when(page_live(c, j))
                    def _():
                        page_dma(0, c, j, slot).start()
                        page_dma(1, c, j, slot).start()
                        if quantized:
                            scale_dma(0, c, j, slot).start()
                            scale_dma(1, c, j, slot).start()

            def wait_chunk(c, slot):
                for j in range(CP):
                    @pl.when(page_live(c, j))
                    def _():
                        page_dma(0, c, j, slot).wait()
                        page_dma(1, c, j, slot).wait()
                        if quantized:
                            scale_dma(0, c, j, slot).wait()
                            scale_dma(1, c, j, slot).wait()

            start_chunk(c_lo, jax.lax.rem(c_lo, 2) if has_window else 0)

            # per-row online-softmax state: one (m, l, acc) strip per
            # (head, sub-tile), reset for the sub-tiles this row touches.
            # With sinks, the state is seeded as if one virtual zero-value
            # key with logit sinks[h] had already been folded in (m0 = sink,
            # l0 = 1) — exactly _sink_softmax's denominator term.
            def init_tile(st, carry2):
                sl = sub_tile(st)
                for i in range(kvh):
                    if has_sinks:
                        m_scr[i, sl] = sinks_ref[i]
                        l_scr[i, sl] = jnp.ones((QG, 1), jnp.float32)
                    else:
                        m_scr[i, sl] = jnp.full((QG, 1), NEG_INF, jnp.float32)
                        l_scr[i, sl] = jnp.zeros((QG, 1), jnp.float32)
                    acc_scr[i, sl] = jnp.zeros((QG, d), jnp.float32)
                return carry2

            jax.lax.fori_loop(st_lo, st_hi, init_tile, 0)

            def chunk_body(c, carry2):
                slot = jax.lax.rem(c, 2)

                @pl.when(c + 1 < chunks)
                def _():
                    start_chunk(c + 1, jax.lax.rem(c + 1, 2))

                wait_chunk(c, slot)

                if quantized:
                    # dequantize in-register: int8 page chunks -> f32 scaled
                    # by the per-(page, kv-head) rows that DMA'd in with them
                    k = (
                        k_buf[slot].astype(jnp.float32)
                        * ks_buf[slot][:, None, :, None]
                    )
                    v = (
                        v_buf[slot].astype(jnp.float32)
                        * vs_buf[slot][:, None, :, None]
                    )
                else:
                    k = k_buf[slot].astype(jnp.float32)
                    v = v_buf[slot].astype(jnp.float32)
                k = k.reshape(T, kvh, d)
                v = v.reshape(T, kvh, d)
                # pages past kv_end were never DMA'd (garbage / NaN): scores
                # are masked below, but V must be zeroed too — 0-weight * NaN
                # = NaN. Same for pages a sliding window skipped at the head.
                row_pos = c * T + jax.lax.broadcasted_iota(
                    jnp.int32, (T, 1, 1), 0
                )
                v_live = row_pos < kv_end
                if has_window:
                    v_live = jnp.logical_and(v_live, row_pos >= lo_page * bs)
                v = jnp.where(v_live, v, 0.0)
                # head select in VMEM, once per chunk (loop-invariant for
                # the sub-tile loop below)
                k_heads = [k[:, i, :] for i in range(kvh)]
                v_heads = [v[:, i, :] for i in range(kvh)]
                key_pos = c * T + jax.lax.broadcasted_iota(
                    jnp.int32, (1, T), 1
                )

                def tile_body(st, carry3):
                    tok = tile_tokens(st)
                    member = jnp.logical_and(tok >= a, tok < b)
                    q_pos = tok + off
                    lim = jnp.where(member, jnp.minimum(q_pos + 1, seq_len), 0)
                    # causal tile-skip: this chunk's keys start at c*T; the
                    # tile's highest attention limit is its last token's
                    tile_lo = blk_lo + st * q_seg
                    do_tile = c * T < jnp.minimum(tile_lo + q_seg + off, kv_end)
                    if has_window:
                        # window tile-skip: a chunk whose last key is below
                        # the window of the tile's EARLIEST member query
                        # contributes nothing to any row of the tile
                        q_pos_min = jnp.maximum(tile_lo, a) + off
                        do_tile = jnp.logical_and(
                            do_tile,
                            jnp.where(
                                w > 0, (c + 1) * T > q_pos_min - w + 1, True
                            ),
                        )

                    @pl.when(do_tile)
                    def _():
                        sl = sub_tile(st)
                        valid = key_pos < lim
                        if has_window:
                            lo = jnp.where(
                                jnp.logical_and(member, w > 0),
                                q_pos - w + 1, 0,
                            )
                            valid = jnp.logical_and(valid, key_pos >= lo)
                        for i in range(kvh):
                            qf = q_ref[i, sl, :].astype(jnp.float32) * scale
                            s = jax.lax.dot_general(
                                qf, k_heads[i],
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )                                      # [QG, T]
                            if softcap is not None:
                                s = jnp.tanh(s / softcap) * softcap
                            s = jnp.where(valid, s, NEG_INF)
                            m_prev = m_scr[i, sl]
                            l_prev = l_scr[i, sl]
                            m_cur = jnp.max(s, axis=-1, keepdims=True)
                            m_new = jnp.maximum(m_prev, m_cur)
                            # a tile can hold an all-masked score row (a
                            # neighbouring row's token, or a window that
                            # starts mid-chunk): exp(NEG_INF - NEG_INF)
                            # would be 1, so masked lanes are zeroed
                            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                            alpha = jnp.exp(m_prev - m_new)
                            m_scr[i, sl] = m_new
                            l_scr[i, sl] = alpha * l_prev + jnp.sum(
                                p, axis=-1, keepdims=True
                            )
                            acc_scr[i, sl] = (
                                alpha * acc_scr[i, sl]
                                + jax.lax.dot_general(
                                    p, v_heads[i],
                                    dimension_numbers=(((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                )
                            )
                    return carry3

                jax.lax.fori_loop(st_lo, st_hi, tile_body, 0)
                return carry2

            jax.lax.fori_loop(c_lo, chunks, chunk_body, 0)

            def emit_tile(st, carry2):
                sl = sub_tile(st)
                tok = tile_tokens(st)
                member = jnp.logical_and(tok >= a, tok < b)
                for i in range(kvh):
                    out = acc_scr[i, sl] / jnp.maximum(l_scr[i, sl], 1e-30)
                    # masked merge: a sub-tile can span a neighbouring
                    # row's tokens — their already-written outputs survive
                    cur = o_ref[i, sl, :].astype(jnp.float32)
                    o_ref[i, sl, :] = jnp.where(member, out, cur).astype(
                        o_ref.dtype
                    )
                return carry2

            jax.lax.fori_loop(st_lo, st_hi, emit_tile, 0)

        return carry

    jax.lax.fori_loop(0, num_rows, row_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "q_seg", "q_block", "chunk_tokens", "interpret", "softcap"
    ),
)
def ragged_paged_attention(
    q: jax.Array,             # [Tq, h, d] densely packed ragged queries
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d] (or QuantizedKV)
    v_cache: jax.Array,
    block_tables: jax.Array,  # [R, max_blocks] int32
    q_starts: jax.Array,      # [R] int32
    q_lens: jax.Array,        # [R] int32 (0 = empty row)
    seq_lens: jax.Array,      # [R] int32
    *,
    windows: jax.Array = None,   # [R] int32 per-row window (<=0 = full)
    sinks: jax.Array = None,     # [h] f32 per-head sink logits
    softcap: float = None,       # static logit softcap (gemma-2)
    q_seg: int = None,
    q_block: int = Q_BLOCK,
    chunk_tokens: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Unified ragged paged attention (Pallas). Same semantics as
    ``ops.attention.ragged_paged_attention`` (the pure-JAX reference twin):
    row r's segment ``q[q_starts[r] : q_starts[r]+q_lens[r]]`` attends
    causally over that row's pages with the segment at the context tail;
    tokens outside every segment return zeros. Optional per-row
    ``windows`` (sliding-window bounds), per-head ``sinks`` logits, and a
    static ``softcap`` extend the same launch to the gpt-oss/gemma
    families and spec-verify rows (``q_len = k+1``). ``k_cache``/
    ``v_cache`` may be ``QuantizedKV`` — int8 pages + per-block scale rows
    DMA together and dequantize in-register (interpret mode only)."""
    Tq, h, d = q.shape
    _, bs, kvh, _ = k_cache.shape
    R, max_blocks = block_tables.shape
    g = h // kvh
    chunk_pages = max(1, chunk_tokens // bs)
    quantized = is_quantized(k_cache)
    has_window = windows is not None
    has_sinks = sinks is not None
    if q_seg is None:
        q_seg = default_q_seg(g)

    # pad the packed buffer to whole sub-tiles, and to whole blocks once it
    # spans more than one
    Tq_pad = -(-Tq // q_seg) * q_seg
    if Tq_pad > q_block:
        q_block = -(-q_block // q_seg) * q_seg
        Tq_pad = -(-Tq // q_block) * q_block
    else:
        q_block = Tq_pad
    if Tq_pad != Tq:
        q = jnp.pad(q, ((0, Tq_pad - Tq), (0, 0), (0, 0)))

    kernel = functools.partial(
        _unified_kernel, max_blocks=max_blocks, chunk_pages=chunk_pages,
        q_seg=q_seg, q_block=q_block, num_rows=R, quantized=quantized,
        has_window=has_window, has_sinks=has_sinks, softcap=softcap,
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
    scratch += [
        pltpu.VMEM((kvh, q_block * g, 1), jnp.float32),   # m
        pltpu.VMEM((kvh, q_block * g, 1), jnp.float32),   # l
        pltpu.VMEM((kvh, q_block * g, d), jnp.float32),   # acc
    ]
    scratch.append(pltpu.SemaphoreType.DMA((2, 2, chunk_pages)))
    if quantized:
        scratch.append(pltpu.SemaphoreType.DMA((2, 2, chunk_pages)))

    # [Tq, h, d] -> [kvh, Tq*g, d]: each kv head's q group contiguous and
    # (token, group)-flattened, so a sub-tile is a dense [q_seg*g, d] slab
    qg = q.reshape(Tq_pad, kvh, g, d).transpose(1, 0, 2, 3).reshape(
        kvh, Tq_pad * g, d
    )
    qo_spec = pl.BlockSpec((kvh, q_block * g, d), lambda t, *_: (0, t, 0))
    in_specs = [qo_spec]
    inputs = [qg]
    if has_sinks:
        # head kh*g + gi's sink logit, tiled over the q_seg tokens of a
        # sub-tile in the same (token, group) row order as q
        in_specs.append(
            pl.BlockSpec((kvh, q_seg * g, 1), lambda t, *_: (0, 0, 0))
        )
        inputs.append(
            jnp.tile(
                sinks.astype(jnp.float32).reshape(kvh, 1, g), (1, q_seg, 1)
            ).reshape(kvh, q_seg * g, 1)
        )
    in_specs += cache_specs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + (1 if has_window else 0),
        grid=(Tq_pad // q_block,),
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=scratch,
    )
    cache_args = (
        (k_cache.data, v_cache.data, k_cache.scale, v_cache.scale)
        if quantized else (k_cache, v_cache)
    )
    prefetch = [
        q_starts.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
    ]
    if has_window:
        prefetch.append(windows.astype(jnp.int32))
    prefetch.append(block_tables.reshape(-1).astype(jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, Tq_pad * g, d), q.dtype),
        interpret=interpret,
        # the windowed launch under a name of its own: the device trace
        # tells a sliding layer's calls from a full layer's
        name=KERNEL_NAME_WINDOWED if has_window else KERNEL_NAME,
    )(*prefetch, *inputs, *cache_args)
    # [kvh, Tq_pad*g, d] -> [Tq, h, d]
    return out.reshape(kvh, Tq_pad, g, d).transpose(1, 0, 2, 3).reshape(
        Tq_pad, h, d
    )[:Tq]


def sharded_ragged_paged_attention(
    mesh: Mesh,
    tp_axis: str,
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    q_starts: jax.Array,
    q_lens: jax.Array,
    seq_lens: jax.Array,
    *,
    windows: jax.Array = None,
    sinks: jax.Array = None,
    **kw,
) -> jax.Array:
    """TP-sharded wrapper: attention is head-wise independent, so each TP
    shard runs the kernel on its own heads (q sharded on h, caches on kvh,
    sink logits on their head dim; per-row windows replicate). shard_map
    because GSPMD cannot partition a custom call — the same treatment as
    the split kernels' sharded wrappers."""
    if mesh.shape[tp_axis] == 1:
        return ragged_paged_attention(
            q, k_cache, v_cache, block_tables, q_starts, q_lens, seq_lens,
            windows=windows, sinks=sinks, **kw,
        )
    cache_spec = P(None, None, tp_axis, None)
    if is_quantized(k_cache):
        # spec tree mirrors the QuantizedKV pytree (payload on kv_heads,
        # scale rows on their kv-head dim) — same as the decode kernel
        cache_spec = QuantizedKV(cache_spec, P(None, tp_axis))
    args = [q, k_cache, v_cache, block_tables, q_starts, q_lens, seq_lens]
    specs = [
        P(None, tp_axis, None),
        cache_spec,
        cache_spec,
        P(None, None),
        P(None),
        P(None),
        P(None),
    ]
    has_window = windows is not None
    has_sinks = sinks is not None
    if has_window:
        args.append(windows)
        specs.append(P(None))
    if has_sinks:
        args.append(sinks)
        specs.append(P(tp_axis))

    def run(q, kc, vc, tables, qs, ql, sl, *rest):
        rest = list(rest)
        win = rest.pop(0) if has_window else None
        snk = rest.pop(0) if has_sinks else None
        return ragged_paged_attention(
            q, kc, vc, tables, qs, ql, sl, windows=win, sinks=snk, **kw
        )

    fn = shard_map(
        run,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=P(None, tp_axis, None),
        check_vma=False,
    )
    return fn(*args)
