"""Pallas TPU kernel: absorbed latent attention over PAGE-CONTIGUOUS rows.

A latent layer without an indexer (models/mla.py, ``index_topk`` 0) attends
every query over EVERY causal key of its context. With ``W_uk`` folded into
the query and ``W_uv`` applied past the softmax, that is multi-query attention
of all ``h`` heads over one ``[c | k_pe]`` row a key, the values the latent
``c`` itself. Neither the ragged nor the decode kernel computes it (they score
each kv "head" of a page on its own) and the sparse kernel gathers tokens;
this one streams a row's pages, whole.

Layout (ops/attention.py): both paged arrays are ``[num_blocks, block_size,
rows, 128]`` in bf16, the latent in the first, ``[k_pe | 0]`` in row 0 of the
second. The launch sees the latent as ``[tokens, rows, 128]``, whole tokens,
and the second array as ``[tokens, rows / 2, 2, 128]``, of which it copies a
token's FIRST tile alone (rows 0 and 1: ``k_pe`` and, where a layer has one,
an index key; the rows behind them are written and read by nothing), as
``paged_index_keys`` does: 1 024 + 512 = 1 536 bytes a key at 512 + 64 lanes,
of which 1 152 are the key (2 048, the whole token of both, until PR 55). In
VMEM a pair of rows shares a 32-bit word, and so does a pair of TOKENS of a
bf16 matrix: a chunk's buffers become ONE ``[chunk tokens, rank + 128]``
matrix ``[c | k_pe | 0]`` by integer moves alone (``_chunk_matrix``: the
word-rows of even and of odd tokens, each a sublane-strided load of whole
registers, a shift, a mask and an or; of the tile's words the low halves are
kept, so whatever row 1 holds stays out): the scores are one product against
it and the values one product against its first ``rank`` lanes.

Rows of a launch, known when it is traced: optionally ONE chunk row (the
first ``n_chunk`` packed queries, at the tail of ``tables[0]``'s context),
then one-token rows, one a table (decode rows; ``q_lens`` 0 = an empty row,
zeros back). A lone chunk, decode rows and the mixed step are the three
shapes of it, each ONE launch, all named ``paged_latent_attention``.

Grid: one program a tile of ``Q_TILE`` chunk queries (``Q_TILE * h`` rows
through the matrix unit at once: a visit of a 1 024-key chunk is its products,
13.6 us = 91% of the matrix unit's peak, the unpack and the copies under them),
then one a decode row (``h`` rows: a visit is its copies, 1.5 MiB since PR 55
(2.7 us for 2 MiB = 94% of the byte peak until then), the products and the
unpack under them; PERF.md section 6, PR 47 has the table, PR 55 the launch
since. Until PR 47 the unpack ran one token a vector
register and was 3.0 us of a decode visit's 5.9, 2.0 of a tile visit's 15.6:
what the records called "the matrix unit streaming 64 rows a load of its
weights" was that). A program walks its row's pages up to
its last query's position in chunks of ``pallas_paged.chunk_pages`` pages, two
slots: chunk ``c + 1`` is in flight while chunk ``c`` is computed. Online
softmax in float32 scratch; only the chunks from a tile's first query's own
position on are masked. The first program zeroes the latent's buffer, so a row
of it holds zeros or a token ever after and a masked key's weight (exactly 0)
never meets a NaN.

How a chunk is read is chosen a chunk, from what the tables hold: the rule
is ``pallas_paged.PageReader``'s since PR 50 (the decode kernel reads by it
too) and ``_LatentPages`` adds only what a page and a run ARE in the
token-row views. The scalar unit issues copy descriptors in the same instruction stream
as the products, so a descriptor costs the launch its issue time whatever it
moves (the bytes themselves arrive at 87% of the byte peak under the products).
A WHOLE chunk whose ``chunk_pages`` table entries are consecutive block ids (a
prompt admitted in one go into a pool that hands out low ids first;
``pallas_paged.chunk_runs``, one compare of the tables in the launch's XLA
wrapper, handed in as a fourth scalar-prefetch operand) is ONE descriptor an
array: the latent's 1 MiB, contiguous in the token view, and the second
array's 512 KiB, 512 bytes a token at a stride of a token (``rows * 256``
bytes). Every other whole chunk is started page by page, two descriptors a
page (the second strided the same way), ``pallas_paged.UNROLL`` pages a
pass; either way it is waited for ONCE an array, on a descriptor of the slot
buffer's size (a DMA semaphore counts bytes). A tail chunk starts and waits
page by page. On a v5e, 64 heads, 25 000-key contexts, launches chained
inside one jit (PERF.md section 6, PR 34, with PR 33's unpack): 8 decode rows
1.199 ms as runs, 1.516
page by page; a 512-query chunk 12.57 / 13.84; a 320-query chunk + 8 rows 9.13
/ 10.24: a page's two descriptors cost a launch about 26 ns, a run's two about
nothing. As runs since PR 47 (section 6, PR 47: a while loop of launches in
one jit, which read the parent 0.891 / 12.40 / 8.20): 0.430 / 10.78 / 6.81,
with the copies alone 0.408 / 2.31 / 1.73. Since PR 55 (section 6, PR 55: the
same loop, parent 0.426 / 10.80 / 6.81): 0.329 / 10.78 / 6.72 as runs, 700
GB/s of what a decode launch copies; page by page 0.452 / 12.00 / 7.56, the
parent's to 0.002 ms: there a decode launch is its descriptors' issue, not its
bytes. The output is bitwise the same whichever way a chunk came in, and
bitwise what it was when the whole token was copied.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_paged as paged
from .attention import LATENT_LANES
from .pallas_paged import NEG_INF

KERNEL_NAME = "paged_latent_attention"
# the same launch under a ``window`` (a sliding layer's latent: a query sees
# its last ``window`` keys). A name of its own: a reader of KERNEL_NAME's
# roofline reckons every causal key and must never count it
WINDOWED_KERNEL_NAME = "windowed_latent_attention"
# chunk queries a program: Q_TILE x heads rows against a chunk's keys. A lone
# 512-query chunk over 25k keys at 64 heads ran 19.4 ms at 8, 15.0 at 16 on a
# v5e (PERF.md section 6, PR 33: what a wider tile halved was the unpack, paid
# once a visit whatever the rows). Since PR 47 a visit at 16 is its products at
# 91% of the matrix unit's peak with the unpack under them (10.78 ms the same
# chunk, 10.78 with no unpack at all), so a wider tile has nothing left to take
Q_TILE = 16
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# tokens a chunk of a WINDOWED launch, at most: a tile of 16 queries sees 528
# keys and a decode row 513, wherever they start in a chunk, so a visit walks
# ``ceil(528 / T) + 1`` chunks at worst: 1 536 keys at the unwindowed rule's
# 512 (rank 1 024), 1 024 at 256
WINDOW_CHUNK_TOKENS = 256


def _chunk_pages(k_cache: jax.Array, mb: int, window: int | None = None) -> int:
    _, bs, n_rows, lanes = k_cache.shape
    cp = paged.chunk_pages(bs, n_rows, lanes, k_cache.dtype, mb)
    if window:
        # whole registers of even and odd tokens: a chunk of 16 tokens or more
        cp = min(cp, max(WINDOW_CHUNK_TOKENS // bs, -(-16 // bs)))
    return cp


def chunk_reads(k_cache: jax.Array, tables: jax.Array, q_lens: jax.Array,
                seq_lens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """What a launch over these rows reads by the chunk: the whole chunks
    under its rows' contexts (a row with no query reads nothing; a chunk row
    counts once, whatever its tiles), and those of them read as runs."""
    bs = k_cache.shape[1]
    cp = _chunk_pages(k_cache, tables.shape[1])
    n_pages = jnp.where(q_lens > 0, -(-seq_lens // bs), 0)
    runs = paged.chunk_runs(tables, cp)
    read = jnp.arange(runs.shape[1])[None, :] < (n_pages // cp)[:, None]
    return jnp.sum(read), jnp.sum(read & runs)


def _pack_pairs(even, odd):
    """``[n, 128]`` uint32 word-rows of ``n`` even and ``n`` odd tokens (a
    word holds two rows of its token: row ``2w`` the low half, ``2w + 1`` the
    high half) -> those two rows as bf16 matrices ``[2n, 128]`` in the words
    VMEM keeps such a matrix in (token ``2i`` the low half of word-row ``i``,
    ``2i + 1`` the high half): the same bits moved, no float touched."""
    low, high = jnp.uint32(0xFFFF), jnp.uint32(0xFFFF0000)
    return (odd << 16) | (even & low), (odd & high) | (even >> 16)


def _chunk_matrix(k_words, v_words, kcat, slot, T: int, lat_rows: int):
    """The chunk in ``slot`` as ``[c | k_pe | 0]``, written into ``kcat``
    (``[T / 2, rank + 128]`` uint32: the words of a ``[T, rank + 128]`` bf16
    matrix). ``k_words`` / ``v_words``: the page buffers as ``[2 T rows / 2,
    128]`` / ``[2 T, 128]`` uint32, a token's word-rows one after the other
    (the latent's ``rows / 2``, the second array's one tile), so a word-row of
    every second token is ONE sublane-strided load a vector register, eight
    tokens in it. Keep the read 2-D and strided: a slice ``[slot, :, w, :]``
    of the 4-D buffer comes back one token a register with a rotate and a
    select a token, 45 000 vector instructions a visit where these are 3 600
    (PERF.md section 6, PR 47; tests/test_tpu_compile.py counts them)."""
    nw = lat_rows // 2                       # the latent's word-rows a token
    rank = lat_rows * LATENT_LANES

    def pairs(words, w, nw):
        """Word-row ``w`` of the ``nw`` a token of ``words`` holds."""
        base = slot * (T * nw)
        return _pack_pairs(
            words[pl.ds(base + w, T // 2, stride=2 * nw), :],
            words[pl.ds(base + nw + w, T // 2, stride=2 * nw), :],
        )

    for w in range(nw):
        for half, x in enumerate(pairs(k_words, w, nw)):
            lane0 = (2 * w + half) * LATENT_LANES
            kcat[:, lane0:lane0 + LATENT_LANES] = x
    # the tile's low halves are k_pe; its high halves (row 1: an index key,
    # or whatever the pool holds there) stay out of the matrix
    kcat[:, rank:] = pairs(v_words, 0, 1)[0]


class _LatentPages(paged.PageReader):
    """A chunk's copies out of the token views: a page is ``bs`` tokens, a run
    of pages one stretch of tokens; of the latent whole tokens, of the second
    array each token's first tile (a strided descriptor, ONE a page or a run
    all the same). Which chunk goes which way, and the one wait an array, are
    the base class's."""

    def __init__(self, tables_ref, runs_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
                 bs, cp):
        super().__init__(tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
                         runs_ref=runs_ref, chunk_pages=cp)
        self.bs = bs

    def _tokens(self, slot, src, *dst):
        """Descriptors of the tokens ``src`` into ``slot`` (at ``dst``)."""
        (k_hbm, k_buf, k_sem), (v_hbm, v_buf, v_sem) = self.pairs
        return [
            pltpu.make_async_copy(
                k_hbm.at[src], k_buf.at[slot, *dst], k_sem.at[slot]),
            pltpu.make_async_copy(
                v_hbm.at[src, 0], v_buf.at[slot, *dst], v_sem.at[slot]),
        ]

    def copies(self, slot, idx, j):
        return self._tokens(
            slot, pl.ds(idx * self.bs, self.bs), pl.ds(j * self.bs, self.bs))

    def run_copies(self, slot, idx):
        return self._tokens(slot, pl.ds(
            pl.multiple_of(idx * self.bs, self.bs), self.cp * self.bs))


def _kernel(lens_ref, qlens_ref, tables_ref, runs_ref, *refs, bs: int,
            cp: int, mb: int, lat_rows: int, scale: float, n_ct: int, qt: int,
            n_one: int, window: int):
    # scalar prefetch (SMEM): lens [R] context lengths, qlens [R] query
    # lengths, tables [R * mb], runs [R * (mb // cp)] (chunk_runs)
    it = iter(refs)
    qc_ref = next(it) if n_ct else None    # VMEM [qt, h, rank + 128]
    q1_ref = next(it) if n_one else None   # VMEM [1, h, rank + 128]
    k_hbm = next(it)        # ANY/HBM [nb * bs, rows, 128] the latent
    v_hbm = next(it)        # ANY/HBM [nb * bs, rows / 2, 2, 128]; [t, 0] =
    #                         the tile [k_pe | index key], rows 0 and 1
    oc_ref = next(it) if n_ct else None    # VMEM [qt, h, rank]
    o1_ref = next(it) if n_one else None   # VMEM [1, h, rank]
    k_buf = next(it)        # VMEM [2, T, rows, 128] bf16
    v_buf = next(it)        # VMEM [2, T, 2, 128] bf16: that tile a token
    kcat = next(it)         # VMEM [T / 2, rank + 128] uint32: the words of
    #                         the bf16 matrix [T, rank + 128] = [c | k_pe | 0]
    m_scr = next(it)        # VMEM [M, 1] f32
    l_scr = next(it)        # VMEM [M, 1] f32
    acc_scr = next(it)      # VMEM [M, rank] f32
    sem = next(it)          # DMA sems [2 (k / v), 2 (slot)]

    T = cp * bs
    rank = lat_rows * LATENT_LANES
    h = (qc_ref if n_ct else q1_ref).shape[1]
    i = pl.program_id(0)
    pages = _LatentPages(
        tables_ref, runs_ref, k_hbm, v_hbm, k_buf, v_buf, sem, bs, cp)
    # both slots as word-rows: a token's rows / 2 one after the other, and
    # its one tile of the second array
    k_words = k_buf.bitcast(jnp.uint32).reshape(
        2 * T * (lat_rows // 2), LATENT_LANES)
    v_words = v_buf.bitcast(jnp.uint32).reshape(2 * T, LATENT_LANES)
    kmat = kcat.bitcast(k_buf.dtype)        # [T, rank + 128] bf16
    n_runs = mb // cp                       # runs_ref entries a row

    @pl.when(i == 0)
    def _clean():
        # a masked key's weight is an exact 0, and 0 * NaN = NaN: a row of
        # the buffer holds zeros until it holds a token, never what VMEM
        # held before the launch
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)

    def attend(r, q, q_pos0, n_valid, kv_end):
        """``q [M, rank + 128]``: ``M / h`` queries of row ``r``, all heads,
        the first at position ``q_pos0``, ``n_valid`` of them real; keys
        below ``kv_end``. Returns ``[M, rank]`` f32, zeros for the others."""
        M = q.shape[0]
        rows = pl.ds(0, M)
        m_scr[rows] = jnp.full((M, 1), NEG_INF, jnp.float32)
        l_scr[rows] = jnp.zeros((M, 1), jnp.float32)
        acc_scr[rows] = jnp.zeros((M, rank), jnp.float32)
        n_pages = pl.cdiv(kv_end, bs)
        n_chunks = pl.cdiv(n_pages, cp)
        tok = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // h
        real = tok < n_valid
        q_pos = q_pos0 + tok

        def count(c):
            return jnp.minimum(cp, n_pages - c * cp)

        # under a window the walk starts at the chunk that holds the first
        # query's oldest visible key, ``q_pos0 - window + 1``: the chunks
        # before it hold keys no query of these sees
        if window:
            c_lo = jnp.minimum(
                jnp.maximum(q_pos0 - window + 1, 0) // T, n_chunks)

            @pl.when(n_chunks > c_lo)
            def _first():
                pages.start(
                    r * mb + c_lo * cp, count(c_lo), 0, r * n_runs + c_lo)
        else:
            c_lo = 0

            @pl.when(n_chunks > 0)
            def _first():
                pages.start(r * mb, count(0), 0, r * n_runs)

        def chunk(c, carry, *, masked):
            slot = jax.lax.rem(c - c_lo, 2) if window else jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_chunks)
            def _next():
                pages.start(r * mb + (c + 1) * cp, count(c + 1), 1 - slot,
                            r * n_runs + c + 1)

            pages.wait(count(c), slot)
            _chunk_matrix(k_words, v_words, kcat, slot, T, lat_rows)
            s = jax.lax.dot_general(
                q, kmat[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                           # [M, T]
            if masked:
                key = c * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                valid = jnp.logical_and(key <= q_pos, real)
                if window:
                    valid = jnp.logical_and(valid, key > q_pos - window)
                s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[rows]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                # a query that is not real has no key it sees:
                # exp(NEG_INF - NEG_INF) would be 1
                p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[rows] = m_new
            l_scr[rows] = alpha * l_scr[rows] + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_scr[rows] = alpha * acc_scr[rows] + jnp.dot(
                p.astype(kmat.dtype), kmat[:, :rank],
                preferred_element_type=jnp.float32,
            )
            return carry

        # chunks that end at or below the first query's position hold only
        # keys every query sees, and no row never read
        if window:
            # a window is three or four chunks, most with an edge of it (the
            # lower or the causal one): every chunk is masked
            c_tail = c_lo
        else:
            c_tail = jnp.clip(q_pos0 // T, 0, n_chunks)
            jax.lax.fori_loop(
                0, c_tail, functools.partial(chunk, masked=False), 0)
        jax.lax.fori_loop(
            c_tail, n_chunks, functools.partial(chunk, masked=True), 0)
        l = l_scr[rows]
        out = acc_scr[rows] / jnp.where(l > 0, l, 1.0)
        return jnp.where(real, out, 0.0)

    if n_ct:
        @pl.when(i < n_ct)
        def _tile():
            q_len, seq_len = qlens_ref[0], lens_ref[0]
            t0 = i * qt
            n_valid = jnp.where(
                seq_len > 0, jnp.clip(q_len - t0, 0, qt), 0)
            q_pos0 = seq_len - q_len + t0
            kv_end = jnp.where(n_valid > 0, q_pos0 + n_valid, 0)
            W = qc_ref.shape[2]
            out = attend(
                0, qc_ref[...].reshape(qt * h, W), q_pos0, n_valid, kv_end)
            oc_ref[...] = out.reshape(qt, h, rank).astype(oc_ref.dtype)

    if n_one:
        @pl.when(i >= n_ct)
        def _row():
            r = i - n_ct + (1 if n_ct else 0)
            seq_len = lens_ref[r]
            live = jnp.logical_and(qlens_ref[r] > 0, seq_len > 0)
            out = attend(
                r, q1_ref[0], seq_len - 1, jnp.where(live, 1, 0),
                jnp.where(live, seq_len, 0),
            )
            o1_ref[0] = out.astype(o1_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "n_chunk", "interpret", "window", "name"),
)
def paged_latent_attention(
    q: jax.Array,            # [Tq, h, rank + 128]: [absorbed q | q_pe | 0]
    k_cache: jax.Array,      # [nb, bs, rows, 128] bf16 the latent
    v_cache: jax.Array,      # [nb, bs, rows, 128] bf16, row 0 = [k_pe | 0]
    tables: jax.Array,       # [R, mb] int32
    q_lens: jax.Array,       # [R] the chunk's real length, then 0 / 1 a row
    seq_lens: jax.Array,     # [R] context lengths incl. the row's queries
    *, scale: float, n_chunk: int = 0, interpret: bool = False,
    window: int | None = None, name: str = KERNEL_NAME,
) -> jax.Array:
    """ops/attention.paged_latent_attention has the contract; returns
    [Tq, h, rank]. The first ``n_chunk`` queries are row 0's chunk (its
    real queries first), every later query is a row of its own. The second
    array has rows of its own (at least the one tile a key's copy reads)."""
    Tq, h, width = q.shape
    nb, bs, n_rows, lanes = k_cache.shape
    R, mb = tables.shape
    rank = width - lanes
    lat_rows = rank // lanes
    if (k_cache.dtype != jnp.bfloat16 or q.dtype != jnp.bfloat16
            or lanes != LATENT_LANES or rank % (2 * lanes)
            or n_rows != lat_rows or bs % 2 or v_cache.shape[2] % 2
            or v_cache.shape[:2] != (nb, bs)):
        raise ValueError(
            "paged_latent_attention reads bf16 pages of 128 lanes a row, the "
            "latent an even number of rows, a page an even number of tokens; "
            f"got {k_cache.dtype} {k_cache.shape}, q {q.dtype}, latent rank "
            f"{rank}"
        )
    n_one = Tq - n_chunk
    if n_one != R - (1 if n_chunk else 0):
        raise ValueError(
            f"{Tq} queries of which {n_chunk} are a chunk do not make {R} rows"
        )
    qt = Q_TILE
    n_ct = -(-n_chunk // qt)
    cp = _chunk_pages(k_cache, mb, window)
    T = cp * bs
    tables = tables.astype(jnp.int32)
    M = max(qt * h if n_ct else 0, h)

    operands, in_specs, out_shapes, out_specs = [], [], [], []
    if n_ct:
        qc = jnp.pad(q[:n_chunk], ((0, n_ct * qt - n_chunk), (0, 0), (0, 0)))
        operands.append(qc)
        tile = lambda i, *_: (jnp.minimum(i, n_ct - 1), 0, 0)  # noqa: E731
        in_specs.append(pl.BlockSpec((qt, h, width), tile))
        out_shapes.append(jax.ShapeDtypeStruct((n_ct * qt, h, rank), q.dtype))
        out_specs.append(pl.BlockSpec((qt, h, rank), tile))
    if n_one:
        operands.append(q[n_chunk:])
        row = lambda i, *_: (jnp.maximum(i - n_ct, 0), 0, 0)  # noqa: E731
        in_specs.append(pl.BlockSpec((1, h, width), row))
        out_shapes.append(jax.ShapeDtypeStruct((n_one, h, rank), q.dtype))
        out_specs.append(pl.BlockSpec((1, h, rank), row))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    outs = pl.pallas_call(
        functools.partial(
            _kernel, bs=bs, cp=cp, mb=mb, lat_rows=lat_rows, scale=scale,
            n_ct=n_ct, qt=qt, n_one=n_one, window=int(window or 0),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_ct + n_one,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, T, n_rows, lanes), k_cache.dtype),
                pltpu.VMEM((2, T, 2, lanes), v_cache.dtype),
                pltpu.VMEM((T // 2, width), jnp.uint32),
                pltpu.VMEM((M, 1), jnp.float32),
                pltpu.VMEM((M, 1), jnp.float32),
                pltpu.VMEM((M, rank), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            # the zeroed buffer persists across the grid's programs, in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(
        seq_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
        tables.reshape(-1),
        paged.chunk_runs(tables, cp).reshape(-1).astype(jnp.int32),
        *operands,
        k_cache.reshape(nb * bs, n_rows, lanes),
        v_cache.reshape(nb * bs, v_cache.shape[2] // 2, 2, lanes),
    )
    parts = []
    if n_ct:
        parts.append(outs[0][:n_chunk])
    if n_one:
        parts.append(outs[-1])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
