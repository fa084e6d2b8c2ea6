"""Pallas TPU kernel: unified ragged paged attention (prefill + decode fused).

ONE launch serves an arbitrary mix of prefill chunks and decode tokens — the
"Ragged Paged Attention" formulation (PAPERS.md): a lone prefill chunk (one
row), a chunk fused with the resident decode batch (the mixed step),
spec-verify rows, and the decode rows of windowed / sink / softcap layers.
ops/paged_attention.py launches it for every question a step program asks
except unwindowed decode rows, which the decode-only kernel
(ops/pallas_attention.py) serves. Rows carry ``(query_len, seq_len)``
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

There is no gather: KV pages stream straight from the paged cache, and only
the real pages below each query block's causal limit (and above a row's
window) are ever touched. ``ops/costs.unified_attention_bytes`` turns a
launch into a byte count.

Layout: paged cache ``[num_blocks, block_size, kv_heads, head_dim]``, shared
with the decode kernel and the transfer plane, handed over viewed as
``[num_blocks, block_size * kv_heads, head_dim]`` (the same bytes) as the
decode kernel does: a page moves as ONE whole DMA (Mosaic tiles the cache's
two minor dims together, so a single head cannot be sliced out of a page in
HBM) and a chunk of pages lands in VMEM as one dense ``[tokens * kv_heads,
head_dim]`` matrix in the cache's dtype. int8 caches (ops/quant.QuantizedKV)
DMA the int8 pages PLUS their per-block ``[kvh]`` f32 scale rows on the same
scalar-prefetched table indices and dequantize in-register; that scale-row
copy is interpret-only (Mosaic refuses its unaligned minor dim), so the
engine refuses int8 + Pallas on the TPU backend at construction.

Who writes what this kernel reads: the kernel writes nothing (Mosaic refuses a
sub-page slice in HBM, so a token could only go in as a whole page through
VMEM). The decode rows of every program (``decode``, ``decode_multi``, the
rows behind a ``mixed_step``'s chunk) are one XLA scatter of ``[kv_heads, d]``
tokens at ``(block, offset)`` into the 4-D pool (ops/attention.write_decode_kv):
layout assignment gives that scatter the kernel's tiling and copies nothing. A
chunk's whole pages (``prefill``, ``mixed_step``) are scattered into THIS view,
``[num_blocks, block_size * kv_heads, head_dim]`` (the seam's ``write_chunk``):
written as ``[block_size, kv_heads, head_dim]`` windows of the 4-D pool they
made XLA tile a pool of 4 kv heads over ``(block_size, head_dim)``, and each of
a layer's two arrays was copied to that tiling and back around the launch, a
quarter of the wide-chat cell's device time (PERF.md section 6, PR 40).

Grid: ``(Tq_pad / q_block,)`` — one program per BLOCK of ``q_block`` (128)
packed query tokens, all heads; each block is written by exactly one program
(zeros for tokens no row owns). Inside a program a loop walks the R rows and
skips those with no token in the block. A row's live pages (from the first
page its window can see to the last its last token in the block can) are read
in chunks of ``chunk_pages`` pages, double-buffered: as many as
``pallas_paged.VMEM_CHUNK_BYTES`` holds (512 tokens at 8 kv heads x 128 in
bf16, 1 024 at 4), at most a row's. While a
chunk is computed the row's next chunk is being read; while a row's LAST chunk
is computed, the first chunk of the next row that has tokens in this block is.
One DMA semaphore a slot and kind, page copies issued and waited in loops over
the real page count (the rule, the copies and the own-head bias are
ops/pallas_paged.py's, shared with the decode kernel).

Two regimes, chosen per row and block from the scalars the kernel sees:

- FEW tokens in the block (one: a decode row; up to ``_few_tokens``: a
  spec-verify row): bound by bytes. All kv heads and all the tokens go through
  ONE product a chunk, as in the decode kernel: ``q[tokens * h, d] . K^T`` over
  the dense chunk gives ``[tokens * h, chunk tokens * kv_heads]``, a bias built
  once a program keeps each query head's own kv head's columns, the rest leave
  the softmax as exact zeros. The tokens' q comes from the token-major q block
  and their output goes to the token-major o block. Compiled twice: for one
  token (``h`` rows) and for ``_few_tokens``.
- MANY tokens in the block (a prefill chunk): bound by FLOPs, where the masked
  product would do ``kv_heads`` x the work. The dense chunk is cut into
  per-head ``[tokens, d]`` matrices once a chunk (a 16-bit cache by a strided
  read of the buffer's 32-bit words: one word holds the same lane of two
  adjacent chunk rows, i.e. of kv heads 2j and 2j+1 of a token), then one
  product a kv head runs over a row tile of up to 512 ``(token, group)`` rows
  of the kv-head-major q block against the head's keys, with online-softmax
  state per (head, tile) in VMEM scratch and a masked emit, so rows that share
  a tile keep each other's outputs. Tiles the row does not reach in the block,
  and chunks a tile's tokens cannot see (causally, or below a window), are
  skipped whole.

Where the rule lies and why: with ``n`` tokens of a row in the block the masked
product costs ``n h x chunk tokens x kv_heads`` scores a chunk; the per-head
side costs a whole row tile a kv head however few of its rows are the row's,
plus the cut into heads: 9.8 us a 512-token chunk at 8 kv heads where the
read takes 2.7, against 3.6 us for the masked product of 8 tokens x 16 heads
(two-token rows over 8k contexts on a v5e: PERF.md section 6, PR 27). The
masked product stays near the chunk's read time while it has about 128 rows,
so the rule is ``n <= 128 / h`` (8 tokens at 16 query heads, 4 at 32; never
more than 8), and 1 where ``h`` is not a whole number of sublane tiles of the q
block (4 or 8 heads a shard in bf16: the rows of several tokens are then no
aligned slab).

Q and K meet the matrix unit in the cache's dtype (bf16 x bf16 products are
exact in the f32 accumulator) and ``1/sqrt(d)`` goes on the f32 scores; the
softmax state, ``p``, V and the accumulator are f32. Scores are masked against
the row's end only where such keys can be: on the few tokens' side the chunks
from the earliest token's own position on, on the other a tile's chunks that
reach past its earliest token's position; a windowed launch masks every chunk
(a window's chunks are its two ends, seldom more). Never-read rows of V in a
row's last chunk are zeroed (0 x NaN = NaN). A prefill chunk spanning several
blocks re-streams its causal prefix once per block of ``q_block`` = 128 tokens;
the caller adds the two o blocks, each token written in exactly one of them.
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

# packed query tokens per grid program (one VMEM-resident q/o block)
Q_BLOCK = 128

# rows of one per-head product on the FLOP-bound side: enough to stream
# through a 128 x 128 matrix unit well past the time its weights take to load
_ROW_TILE = 512

KERNEL_NAME = "ragged_paged_attention"
KERNEL_NAME_WINDOWED = "ragged_paged_attention_windowed"


def _tile_tokens(g: int, q_block: int) -> int:
    """Query tokens of one row tile on the per-head side: the power of two
    with ``tokens * g <= _ROW_TILE`` rows, at least 16 (whole packed sublane
    tiles in bf16) and at most a block."""
    t = 16
    while 2 * t * g <= _ROW_TILE and 2 * t <= Q_BLOCK:
        t *= 2
    return min(t, q_block)


def _few_tokens(h: int, dtype, q_block: int) -> int:
    """The most tokens of a row in a block that still take the byte-bound
    side: as many as make the masked product 128 rows (``tokens * h``), at
    most 8 and a block; 1 where ``h`` rows are not whole sublane tiles of
    the q block (several tokens' rows are then no aligned slab)."""
    if h % (32 // jnp.dtype(dtype).itemsize):
        return 1
    return max(1, min(128 // h, 8, q_block))


def _unified_kernel(
    *args,
    max_blocks: int,
    chunk_pages: int,
    q_block: int,
    q_tile: int,
    few: int,
    num_rows: int,
    kvh: int,
    quantized: bool,
    has_window: bool,
    has_sinks: bool,
    packed_heads: bool,
    softcap,
):
    # args layout (optional pieces gated by the static flags):
    #   scalar prefetch (SMEM): starts [R], qlens [R], lens [R],
    #     [windows [R]], tables [R * max_blocks]
    #   inputs: qn VMEM [QB, h, d] (token-major), qh VMEM [kvh, QB*g, d]
    #     (kv-head-major), [sinks_n VMEM [h, 1], sinks_h VMEM [kvh, TM, 1]],
    #     k/v ANY/HBM [num_blocks, bs*kvh, d],
    #     [k/v scales ANY/HBM [num_blocks, kvh] f32]
    #   outputs: on VMEM [QB, h, d], oh VMEM [kvh, QB*g, d]
    #   scratch: k/v_buf VMEM [2, CP, bs*kvh, d], [k/v scale bufs
    #     [2, CP, kvh]], bias VMEM [few*h, N] f32, kh/vh VMEM [kvh, T, d],
    #     m/l/acc VMEM [kvh, QB*g, 1/1/d] f32, DMA sems [2, 2] (+quant)
    it = iter(args)
    starts_ref = next(it)
    qlens_ref = next(it)
    lens_ref = next(it)
    windows_ref = next(it) if has_window else None
    tables_ref = next(it)
    qn_ref = next(it)
    qh_ref = next(it)
    sinks_n_ref = next(it) if has_sinks else None
    sinks_h_ref = next(it) if has_sinks else None
    k_hbm = next(it)
    v_hbm = next(it)
    scales = None
    if quantized:
        ks_hbm = next(it)
        vs_hbm = next(it)
    on_ref = next(it)
    oh_ref = next(it)
    k_buf = next(it)
    v_buf = next(it)
    ks_buf = vs_buf = None
    if quantized:
        ks_buf = next(it)
        vs_buf = next(it)
    bias_ref = next(it)
    kh_scr = next(it)
    vh_scr = next(it)
    m_scr = next(it)
    l_scr = next(it)
    acc_scr = next(it)
    sem = next(it)
    if quantized:
        scales = (ks_hbm, vs_hbm, ks_buf, vs_buf, next(it))

    QB, h, d = qn_ref.shape
    g = h // kvh
    bs = k_hbm.shape[1] // kvh
    CP = chunk_pages
    T = CP * bs             # tokens a chunk
    N = T * kvh             # rows of the dense chunk: (token, kv head) pairs
    TM = q_tile * g         # rows of a row tile: (token, group) pairs
    R = num_rows
    scale = 1.0 / (d ** 0.5)
    blk_lo = pl.program_id(0) * QB
    pages = paged.PageReader(
        tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem, scales
    )

    def span(r):
        """What block ``blk_lo`` holds of row ``r`` (``r`` clamped, so that a
        search may look one past the last row)."""
        r = jnp.minimum(r, R - 1)
        q_start, q_len, seq_len = starts_ref[r], qlens_ref[r], lens_ref[r]
        # [a, b): the row's tokens that fall inside this block
        a = jnp.maximum(q_start, blk_lo)
        b = jnp.minimum(q_start + q_len, blk_lo + QB)
        # packed token index + off = absolute position in the context
        off = seq_len - q_len - q_start
        # keys any member query can see end at the last member's limit
        kv_end = jnp.minimum(b + off, seq_len)
        if has_window:
            # the block's earliest member query (position a + off) sees no
            # key below a + off - w + 1: pages a sliding window already aged
            # out are never read, and the row's chunks start at its first
            # live page
            w = windows_ref[r]
            lo_page = jnp.where(
                w > 0, jnp.maximum(a + off - w + 1, 0) // bs, 0
            )
        else:
            w = None
            lo_page = 0
        live = jnp.logical_and(b > a, seq_len > 0)
        n_pages = jnp.where(live, pl.cdiv(kv_end, bs) - lo_page, 0)
        return dict(
            r=r, a=a, b=b, off=off, kv_end=kv_end, w=w, lo_page=lo_page,
            live=live, n_pages=n_pages,
            chunks=pl.cdiv(n_pages, CP),
        )

    def chunk_count(sp, c):
        return jnp.minimum(CP, sp["n_pages"] - c * CP)

    def start_chunk(sp, c, slot):
        """Chunk ``c`` of a row: pages ``lo_page + c*CP ...`` of its table."""
        pages.start(
            sp["r"] * max_blocks + sp["lo_page"] + c * CP,
            chunk_count(sp, c), slot,
        )

    def start_next_row(row, slot):
        """Start chunk 0 of the first row after ``row`` that has tokens in
        this block, if any: reads stay in flight from row to row."""
        nxt = jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < R, jnp.logical_not(span(r)["live"])),
            lambda r: r + 1,
            row + 1,
        )

        @pl.when(nxt < R)
        def _():
            start_chunk(span(nxt), 0, slot)

    def fetch(sp, slot0, c):
        """Keep the next read in flight (this row's next chunk, or the next
        row's first), then wait for chunk ``c``. Returns the chunk's slot
        and the position of its first key."""
        slot = jax.lax.rem(slot0 + c, 2)

        @pl.when(c + 1 < sp["chunks"])
        def _():
            start_chunk(sp, c + 1, 1 - slot)

        @pl.when(c + 1 == sp["chunks"])
        def _():
            start_next_row(sp["r"], 1 - slot)

        pages.wait(chunk_count(sp, c), slot)
        return slot, (sp["lo_page"] + c * CP) * bs

    def load_chunk(slot):
        """The dense ``[N, d]`` K and V of a chunk (int8: dequantized by the
        per-(page, kv-head) scale rows that DMA'd in alongside)."""
        k = k_buf[slot].reshape(N, d)
        v = v_buf[slot].reshape(N, d)
        if quantized:
            def dequant(x, sc):
                x = x.astype(jnp.float32).reshape(CP, bs, kvh, d)
                return (x * sc[:, None, :, None]).reshape(N, d)

            k, v = dequant(k, ks_buf[slot]), dequant(v, vs_buf[slot])
        return k, v

    start_next_row(-1, 0)
    # tokens of this block that no row owns (gaps between segments, bucket
    # padding) must read back deterministic zeros, matching the twin; a row
    # writes its tokens to ONE of the two outputs and the caller adds them
    on_ref[...] = jnp.zeros_like(on_ref)
    oh_ref[...] = jnp.zeros_like(oh_ref)
    bias_ref[...] = paged.own_head_bias(h, g, kvh, N, tokens=few)

    # --------------------------------------------------------- few tokens
    def few_token_row(sp, slot0, na):
        """The byte-bound side: a row with at most ``na`` tokens in the
        block (``na`` = 1: a decode row; more: a spec-verify row). All kv
        heads AND all the tokens through one masked product a chunk, as the
        decode kernel does (ops/pallas_attention.py): ``[na * h, d]`` against
        the dense chunk."""
        a, b, off, w = sp["a"], sp["b"], sp["off"], sp["w"]
        kv_end = sp["kv_end"]
        M = na * h
        # the na tokens from t0 on hold the row's (t0 clamped into the block)
        t0 = jnp.minimum(a - blk_lo, QB - na)
        if na == 1:
            q = qn_ref[t0]                                      # [h, d]
        else:
            q = qn_ref[pl.ds(t0, na)].reshape(M, d)
        # per slab row: the keys its token sees are [lo, lim) (none for a
        # slab row that is not the row's); scalars for one token
        if na == 1:
            q_pos, lim = a + off, kv_end
            member = True
        else:
            tok = blk_lo + t0 + jax.lax.broadcasted_iota(
                jnp.int32, (M, 1), 0) // h
            member = jnp.logical_and(tok >= a, tok < b)
            q_pos = tok + off
            lim = jnp.where(member, q_pos + 1, 0)
        if has_window:
            lo = jnp.where(jnp.logical_and(member, w > 0), q_pos - w + 1, 0)

        def chunk(c, carry, *, masked):
            m_prev, l_prev, acc_prev = carry
            slot, key0 = fetch(sp, slot0, c)
            k, v = load_chunk(slot)
            v = v.astype(jnp.float32)
            if masked:
                # rows past kv_end were never read (stale / NaN): scores are
                # masked below, but V must be zeroed too: 0 * NaN = NaN
                rows = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
                v = jnp.where(rows < (kv_end - key0) * kvh, v, 0.0)
            qk = q
            if k.dtype != q.dtype:
                qk, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(
                qk, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                           # [M, N]
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            s = s + bias_ref[0:M]
            if masked:
                # chunk row j is key key0 + j // kvh
                key_row = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
                valid = key_row < (lim - key0) * kvh
                if has_window:
                    valid = jnp.logical_and(
                        valid, key_row >= (lo - key0) * kvh
                    )
                s = jnp.where(valid, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            if masked and na > 1:
                # a chunk may hold no key one of the tokens sees (and none
                # at all for a slab row that is not the row's):
                # exp(NEG_INF - NEG_INF) would be 1. A one-token row's every
                # chunk holds a key it sees: its masked columns leave as
                # exact zeros by themselves
                p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                   # [M, d]
            return m_new, l_new, alpha * acc_prev + pv

        if has_sinks:
            # one virtual zero-value key with logit sinks[h] already folded
            # in: exactly _sink_softmax's denominator term
            carry = (
                jnp.tile(sinks_n_ref[...], (na, 1)),
                jnp.ones((M, 1), jnp.float32),
                jnp.zeros((M, d), jnp.float32),
            )
        else:
            carry = (
                jnp.full((M, 1), NEG_INF, jnp.float32),
                jnp.zeros((M, 1), jnp.float32),
                jnp.zeros((M, d), jnp.float32),
            )
        # Which chunks mask: the chunk of the earliest token's own position
        # and those after it (later keys are not for it; the last holds rows
        # never read); those before see every key. Under a window every
        # chunk masks: its first holds keys below the window, and a window
        # seldom spans a chunk that is neither first nor last.
        c_tail = 0
        if not has_window:
            c_tail = (a + off) // T
            carry = jax.lax.fori_loop(
                0, c_tail, functools.partial(chunk, masked=False), carry
            )
        _, l, acc = jax.lax.fori_loop(
            c_tail, sp["chunks"], functools.partial(chunk, masked=True), carry
        )
        out = (acc / l).astype(on_ref.dtype)
        if na == 1:
            on_ref[t0] = out
        else:
            for j in range(na):
                @pl.when(jnp.logical_and(
                    blk_lo + t0 + j >= a, blk_lo + t0 + j < b))
                def _(j=j):
                    on_ref[t0 + j] = out[j * h:(j + 1) * h]

    # ------------------------------------------------------- many tokens
    def split_heads(slot, live_tok):
        """Cut the dense chunk into per-head ``[T, d]`` matrices (K in the
        product's dtype, V in f32) in VMEM scratch, once a chunk. Tokens from
        ``live_tok`` on (a row's last chunk has some) were never read: their
        V rows are zeroed (0 x NaN = NaN)."""
        if packed_heads:
            # 16-bit cache, even kvh: a 32-bit word of the buffer holds the
            # same lane of two adjacent chunk rows, i.e. kv heads 2j and
            # 2j+1 of one token. A strided read of the words takes the pair
            # for every token; the low half shifted up and the high half
            # masked are the two heads' values as exact f32.
            for kind, (buf, dst) in enumerate(
                ((k_buf, kh_scr), (v_buf, vh_scr))
            ):
                words = buf.bitcast(jnp.uint32)     # [2, CP, bs*kvh/2, d]
                for j in range(kvh // 2):
                    wd = words[
                        slot, :, pl.ds(j, bs, stride=kvh // 2), :
                    ].reshape(T, d)
                    for half, bits in enumerate(
                        (wd << 16, wd & jnp.uint32(0xFFFF0000))
                    ):
                        x = pltpu.bitcast(bits, jnp.float32)
                        if kind == 1:
                            tok = jax.lax.broadcasted_iota(
                                jnp.int32, (T, 1), 0)
                            x = jnp.where(tok < live_tok, x, 0.0)
                        dst[2 * j + half] = x.astype(dst.dtype)
            return
        k, v = load_chunk(slot)
        k = k.astype(kh_scr.dtype).reshape(T, kvh, d)
        v = v.astype(jnp.float32).reshape(T, kvh, d)
        tok = jax.lax.broadcasted_iota(jnp.int32, (T, 1, 1), 0)
        v = jnp.where(tok < live_tok, v, 0.0)
        for i in range(kvh):
            kh_scr[i] = k[:, i, :]
            vh_scr[i] = v[:, i, :]

    def many_token_row(sp, slot0):
        """The FLOP-bound side: a row with many tokens in the block (a
        prefill chunk). One product a kv head over a tile of ``q_tile``
        tokens x ``g`` heads against that head's keys of the chunk;
        online-softmax state per (head, tile) in VMEM scratch."""
        a, b, off = sp["a"], sp["b"], sp["off"]
        kv_end, w = sp["kv_end"], sp["w"]
        st_lo = (a - blk_lo) // q_tile
        st_hi = pl.cdiv(b - blk_lo, q_tile)

        def tile_rows(st):
            """Rows [st*TM, (st+1)*TM) of the (token, group)-flat block."""
            return pl.ds(pl.multiple_of(st * TM, TM), TM)

        def tile_tok(st):
            # packed token index per (token, group) row, built in [TM, 1]
            row = jax.lax.broadcasted_iota(jnp.int32, (TM, 1), 0) // g
            return blk_lo + st * q_tile + row

        def for_tiles(fn):
            """``fn(i, st)`` for every kv head and row tile of the row."""
            def head(i, carry):
                def one(st, carry2):
                    fn(i, st)
                    return carry2

                return jax.lax.fori_loop(st_lo, st_hi, one, carry)

            jax.lax.fori_loop(0, kvh, head, 0)

        def init(i, st):
            sl = tile_rows(st)
            if has_sinks:
                m_scr[i, sl] = sinks_h_ref[i]
                l_scr[i, sl] = jnp.ones((TM, 1), jnp.float32)
            else:
                m_scr[i, sl] = jnp.full((TM, 1), NEG_INF, jnp.float32)
                l_scr[i, sl] = jnp.zeros((TM, 1), jnp.float32)
            acc_scr[i, sl] = jnp.zeros((TM, d), jnp.float32)

        for_tiles(init)

        def chunk(c, carry):
            slot, key0 = fetch(sp, slot0, c)
            split_heads(slot, kv_end - key0)
            key_pos = key0 + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

            def update(i, st, masked):
                sl = tile_rows(st)
                qt = qh_ref[i, sl, :].astype(kh_scr.dtype)
                s = jax.lax.dot_general(
                    qt, kh_scr[i],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                                       # [TM, T]
                if softcap is not None:
                    s = jnp.tanh(s / softcap) * softcap
                if masked:
                    tok = tile_tok(st)
                    member = jnp.logical_and(tok >= a, tok < b)
                    q_pos = tok + off
                    lim = jnp.where(
                        member, jnp.minimum(q_pos + 1, kv_end), 0)
                    valid = key_pos < lim
                    if has_window:
                        lo = jnp.where(
                            jnp.logical_and(member, w > 0), q_pos - w + 1, 0)
                        valid = jnp.logical_and(valid, key_pos >= lo)
                    s = jnp.where(valid, s, NEG_INF)
                m_prev = m_scr[i, sl]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                if masked:
                    # a tile can hold an all-masked score row (another
                    # row's token, a window that starts past this chunk):
                    # exp(NEG_INF - NEG_INF) would be 1
                    p = jnp.where(valid, p, 0.0)
                alpha = jnp.exp(m_prev - m_new)
                m_scr[i, sl] = m_new
                l_scr[i, sl] = alpha * l_scr[i, sl] + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_scr[i, sl] = alpha * acc_scr[i, sl] + jax.lax.dot_general(
                    p, vh_scr[i],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

            def tile(i, st):
                tile_lo = blk_lo + st * q_tile
                # positions of the tile's earliest and latest member
                first = jnp.maximum(tile_lo, a) + off
                last = jnp.minimum(tile_lo + q_tile, b) - 1 + off
                # causal skip of whole chunks: the latest member sees no key
                # of a chunk that starts past it
                do_tile = key0 <= last
                if has_window:
                    # nor does the earliest one of a chunk that ends below
                    # its window; a window's chunks are mostly its two ends,
                    # so all of them mask
                    do_tile = jnp.logical_and(do_tile, jnp.where(
                        w > 0, key0 + T > first - w + 1, True))
                else:
                    # a chunk every key of which every member sees (it ends
                    # at or below the earliest one's position, so it holds
                    # no row never read either) needs no mask
                    clear = key0 + T <= first + 1

                    @pl.when(jnp.logical_and(do_tile, clear))
                    def _():
                        update(i, st, False)

                    do_tile = jnp.logical_and(do_tile, jnp.logical_not(clear))

                @pl.when(do_tile)
                def _():
                    update(i, st, True)

            for_tiles(tile)
            return carry

        jax.lax.fori_loop(0, sp["chunks"], chunk, 0)

        def emit(i, st):
            sl = tile_rows(st)
            tok = tile_tok(st)
            member = jnp.logical_and(tok >= a, tok < b)
            out = acc_scr[i, sl] / jnp.maximum(l_scr[i, sl], 1e-30)
            # masked merge: a tile can span a neighbouring row's tokens,
            # whose already-written outputs survive
            cur = oh_ref[i, sl, :].astype(jnp.float32)
            oh_ref[i, sl, :] = jnp.where(member, out, cur).astype(
                oh_ref.dtype)

        for_tiles(emit)

    def row_body(r, slot0):
        sp = span(r)
        n_tok = sp["b"] - sp["a"]
        bounds = [1, few] if few > 1 else [1]
        for lo, hi in zip([0] + bounds, bounds):
            @pl.when(jnp.logical_and(
                sp["live"], jnp.logical_and(n_tok > lo, n_tok <= hi)))
            def _(hi=hi):
                few_token_row(sp, slot0, hi)

        @pl.when(jnp.logical_and(sp["live"], n_tok > bounds[-1]))
        def _():
            many_token_row(sp, slot0)

        return jax.lax.rem(slot0 + sp["chunks"], 2)

    jax.lax.fori_loop(0, R, row_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("q_block", "chunk_tokens", "interpret", "softcap"),
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
    q_block: Optional[int] = None,
    chunk_tokens: Optional[int] = None,
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
    DMA together and dequantize in-register (interpret mode only).
    ``q_block`` and ``chunk_tokens`` override the derived block and chunk
    sizes (tests: several blocks, tiles and chunks over small shapes); no
    call site of the program sets them."""
    Tq, h, d = q.shape
    nb, bs, kvh, _ = k_cache.shape
    R, max_blocks = block_tables.shape
    g = h // kvh
    quantized = is_quantized(k_cache)
    has_window = windows is not None
    has_sinks = sinks is not None
    pages = k_cache.data if quantized else k_cache
    if chunk_tokens is None:
        chunk_pages = paged.chunk_pages(bs, kvh, d, pages.dtype, max_blocks)
    else:
        chunk_pages = max(1, chunk_tokens // bs)
    # the per-head side cuts a 16-bit dense chunk into heads by 32-bit words
    packed_heads = (
        not quantized and pages.dtype.itemsize == 2 and kvh % 2 == 0
        and bs % 8 == 0
    )

    # pad the packed buffer to whole row tiles, and to whole blocks once it
    # spans more than one
    q_tile = _tile_tokens(g, Q_BLOCK if q_block is None else q_block)
    q_block = Q_BLOCK if q_block is None else -(-q_block // q_tile) * q_tile
    Tq_pad = -(-Tq // q_tile) * q_tile
    if Tq_pad > q_block:
        Tq_pad = -(-Tq // q_block) * q_block
    else:
        q_block = Tq_pad
    if Tq_pad != Tq:
        q = jnp.pad(q, ((0, Tq_pad - Tq), (0, 0), (0, 0)))
    T = chunk_pages * bs
    TM = q_tile * g
    few = _few_tokens(h, q.dtype, q_block)
    kh_dtype = pages.dtype if packed_heads else jnp.float32

    kernel = functools.partial(
        _unified_kernel, max_blocks=max_blocks, chunk_pages=chunk_pages,
        q_block=q_block, q_tile=q_tile, few=few, num_rows=R, kvh=kvh,
        quantized=quantized, has_window=has_window, has_sinks=has_sinks,
        packed_heads=packed_heads, softcap=softcap,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    cache_specs = [any_spec, any_spec]
    scratch = [
        pltpu.VMEM((2, chunk_pages, bs * kvh, d), pages.dtype),
        pltpu.VMEM((2, chunk_pages, bs * kvh, d), pages.dtype),
    ]
    if quantized:
        cache_specs += [any_spec, any_spec]  # k/v scales [num_blocks, kvh]
        scratch += [
            pltpu.VMEM((2, chunk_pages, kvh), jnp.float32),
            pltpu.VMEM((2, chunk_pages, kvh), jnp.float32),
        ]
    scratch += [
        pltpu.VMEM((few * h, T * kvh), jnp.float32),      # own-head bias
        pltpu.VMEM((kvh, T, d), kh_dtype),                # K by head
        pltpu.VMEM((kvh, T, d), jnp.float32),             # V by head
        pltpu.VMEM((kvh, q_block * g, 1), jnp.float32),   # m
        pltpu.VMEM((kvh, q_block * g, 1), jnp.float32),   # l
        pltpu.VMEM((kvh, q_block * g, d), jnp.float32),   # acc
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    if quantized:
        scratch.append(pltpu.SemaphoreType.DMA((2, 2)))

    # q twice: token-major [Tq, h, d] as it comes (a one-token row takes its
    # [h, d] slab) and kv-head-major [kvh, Tq*g, d], each kv head's q group
    # contiguous and (token, group)-flat, so a row tile is a dense [TM, d]
    qh = q.reshape(Tq_pad, kvh, g, d).transpose(1, 0, 2, 3).reshape(
        kvh, Tq_pad * g, d
    )
    n_spec = pl.BlockSpec((q_block, h, d), lambda t, *_: (t, 0, 0))
    h_spec = pl.BlockSpec((kvh, q_block * g, d), lambda t, *_: (0, t, 0))
    in_specs = [n_spec, h_spec]
    inputs = [q, qh]
    if has_sinks:
        # head kh*g + gi's sink logit: per head, and tiled over the tokens
        # of a row tile in the same (token, group) row order as qh
        sinks = sinks.astype(jnp.float32)
        in_specs += [
            pl.BlockSpec((h, 1), lambda t, *_: (0, 0)),
            pl.BlockSpec((kvh, TM, 1), lambda t, *_: (0, 0, 0)),
        ]
        inputs += [
            sinks.reshape(h, 1),
            jnp.tile(sinks.reshape(kvh, 1, g), (1, q_tile, 1)).reshape(
                kvh, TM, 1),
        ]
    in_specs += cache_specs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + (1 if has_window else 0),
        grid=(Tq_pad // q_block,),
        in_specs=in_specs,
        out_specs=[n_spec, h_spec],
        scratch_shapes=scratch,
    )

    def rows(cache):  # [nb, bs, kvh, d] -> [nb, bs * kvh, d]: the same bytes
        return cache.reshape(nb, bs * kvh, d)

    cache_args = (
        (rows(k_cache.data), rows(v_cache.data), k_cache.scale, v_cache.scale)
        if quantized else (rows(k_cache), rows(v_cache))
    )
    prefetch = [
        q_starts.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
    ]
    if has_window:
        prefetch.append(windows.astype(jnp.int32))
    prefetch.append(block_tables.reshape(-1).astype(jnp.int32))
    itemsize = jnp.dtype(q.dtype).itemsize
    vmem = (
        4 * chunk_pages * bs * kvh * d * pages.dtype.itemsize   # page slots
        + few * h * T * kvh * 4                                 # bias
        + kvh * T * d * (jnp.dtype(kh_dtype).itemsize + 4)      # K, V by head
        + kvh * q_block * g * (2 * 128 + d) * 4                 # m, l, acc
        + 2 * 4 * q_block * h * d * itemsize                    # q, o blocks
    )
    out_n, out_h = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Tq_pad, h, d), q.dtype),
            jax.ShapeDtypeStruct((kvh, Tq_pad * g, d), q.dtype),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the buffers above plus room for a tile's scores and weights
            vmem_limit_bytes=vmem + 16 * 1024 * 1024,
        ),
        # the windowed launch under a name of its own: the device trace
        # tells a sliding layer's calls from a full layer's
        name=KERNEL_NAME_WINDOWED if has_window else KERNEL_NAME,
    )(*prefetch, *inputs, *cache_args)
    # a token is written in exactly one of the two (zeros in the other)
    out = out_n + out_h.reshape(kvh, Tq_pad, g, d).transpose(
        1, 0, 2, 3).reshape(Tq_pad, h, d)
    return out[:Tq]


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
    the decode kernel's sharded wrapper."""
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
