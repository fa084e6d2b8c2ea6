"""Pallas TPU kernel: latent attention over the token rows a query selected.

Learned sparse attention (models/mla.py, DSA) gives every query its own list
of at most ``index_topk`` absolute positions. Neither page-contiguous kernel
computes that: this one brings the selected TOKENS into a chunk buffer and
runs the absorbed heads as MQA over them with an online softmax. A token's
latent is read once: the values are the same rows as the keys (``W_uv`` is
applied past the softmax by the model).

Layout (ops/attention.py has the twin and the layout's description): both
paged arrays are ``[num_blocks, block_size, rows, 128]`` in bf16, so a token
of either is whole ``(2, 128)`` tiles of packed pairs of rows and can be the
source of a copy of its own; 576 lanes (512 + 64) cannot: Mosaic slices HBM
by whole tiles. The launch hands the kernel two free views of them: the
latent as ``[tokens, rows, 128]`` (one address from a token row, no page
arithmetic) and the second array as ``[tokens, rows / 2, 2, 128]``, whose
``[t, 0]`` is the one tile that holds ``k_pe`` and the index key: 512 bytes of
it are read, not the token's whole KB. In VMEM a pair of rows shares a 32-bit
word (row ``2w`` the low half, ``2w + 1`` the high half), so the buffers are
read as ``uint32`` and each half becomes one ``[tokens, 128]`` bf16 matrix by
a shift or a mask: ``rows`` matrices of the latent and one of ``k_pe`` (the
index key beside it in the word is dropped).

Grid: one program a query, in order. A query's selected rows are walked in
chunks of ``CHUNK`` tokens in two slots: chunk ``c + 1`` is brought in before
chunk ``c`` is computed. The number selected is scalar-prefetched and a query
with none brings nothing and returns zeros. The first program zeroes the
latent's buffer, so a row holds zeros or a token ever after and a masked key
(weight exactly 0) needs no mask of its values. A chunk buffer is filled in
one of two ways, the products after it are the same:

- **gathered** (decode rows, and every query of a launch whose table is too
  wide to stage): one copy a token and array from HBM, ``UNROLL`` tokens a
  pass of the issue loop, all of a slot's copies signalling one DMA
  semaphore an array. A DMA semaphore counts bytes landed, so a whole chunk
  is waited for ONCE an array, on a descriptor as large as the slot's buffer;
  the tail chunk is padded to whole groups of ``UNROLL`` with the token row
  the launch gives padding (a real row, masked out of the softmax) and waits
  a group at a time. Two descriptor operations a (query, key) pair where
  PR 31 made four (PERF.md section 6, PR 32: 64 -> 44 ns a pair).
- **staged** (the first ``n_chunk`` queries of a launch, which the seam says
  sit in ONE row's context): the first program copies that row's pages into
  VMEM once, page by page (the latent's pages whole, the second array's
  first tile), and it stays there across the grid's programs; a chunk query's
  token rows are then positions in that copy and its buffer is filled by
  loads and stores, no descriptor (11 ns a pair beside the products' 16,
  and they overlap). Taken when ``tables.shape[1] * block_size`` tokens of
  1.5 KB fit ``STAGED_VMEM_BYTES``: a shape of the launch, no flag.

Every launch carries the name ``sparse_latent_attention``: the device trace
and the benchmark's roofline reader find it by that name.

A second launch of this file, ``paged_index_keys``, serves the selection in
front of the attention: it copies that same ``[t, 0]`` tile of every token of
each table's pages (the twin ``ops/attention.paged_index_keys`` slices row 1
out of the 4-D pool, which XLA cannot do in place: it re-tiles the whole
array first, 235 MB in and out at GLM-5.2's pool, every ``full`` layer of
every step; PERF.md section 6, PR 48) and writes the index keys, the high
halves of the tile's words, as the dense ``[R, mb * bs, 128]`` bf16 array the
scoring product takes. Grid: one program a chunk of ``INDEX_CHUNK_PAGES``
pages of one table, in order, two slots: the next program's chunk is in
flight while this one is unpacked. A whole chunk whose table entries are
consecutive block ids (``pallas_paged.chunk_runs``, scalar-prefetched) is ONE
strided descriptor, any other ``INDEX_UNROLL`` pages a pass, both waited for
once (a DMA semaphore counts bytes); a table's tail chunk goes page by page.
The unpack is integer moves on whole registers (PR 47's lesson,
``pallas_latent._chunk_matrix``): a token is one word-row of the buffer read
2-D, even and odd tokens come in as two sublane-strided loads and leave as
the words of a bf16 matrix, ``(odd & 0xFFFF0000) | (even >> 16)``; no float
is touched, so whatever ``k_pe`` holds beside a key (a NaN too) stays out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import LATENT_LANES, selected_token_rows
from .pallas_latent import _pack_pairs
from .pallas_paged import NEG_INF, chunk_runs

KERNEL_NAME = "sparse_latent_attention"
# selected tokens a chunk: 2 slots x (rows + 2) x 256 x 256 B. 512 reads level
# with it on a v5e (8 decode rows 0.807 / 0.809 ms, PERF.md section 6, PR 32)
CHUNK = 256
# tokens a pass of the loop that fills a chunk buffer, and the group a tail
# chunk is padded to and waited for by: a 512 + 8 query launch ran 81.6 /
# 72.2 / 67.1 ms at 1 / 4 / 16 (PERF.md section 6, PR 31)
UNROLL = 16
# the most a launch stages of one row's context (a v5e core has 128 MiB of
# VMEM; the buffers, the query blocks and Mosaic's own scratch take the rest)
STAGED_VMEM_BYTES = 96 * 1024 * 1024

INDEX_KERNEL_NAME = "paged_index_keys"
# pages a program of ``paged_index_keys`` (a chunk: 512 B a token in, 256
# out). 8 tables x 1 600 pages on a v5e, launches chained in one jit (PERF.md
# section 6, PR 48): 0.172 / 0.147 / 0.143 / 0.148 ms as runs at 32 / 64 /
# 128 / 320 pages (the copies alone 0.140: the unpack hides under them),
# 0.250 / 0.232 / 0.226 / 0.217 page by page. 64 keeps more of a churned
# pool's chunks runs than a wider one would, for what 128 gives back
INDEX_CHUNK_PAGES = 64
# pages a pass of the loop that starts a whole chunk that is not a run
# (``pallas_paged.UNROLL``'s measurement: the descriptors are the cost)
INDEX_UNROLL = 8
# tokens a step of the unpack: 32 vector registers of even and of odd tokens
INDEX_UNPACK_TOKENS = 512


def _check_rows_pool(name: str, cache: jax.Array, rank: int = 0):
    """Both launches read bf16 pages of 128 lanes a row, an even number of
    rows a token (whole ``(2, 128)`` tiles)."""
    lanes, n_rows = cache.shape[3], cache.shape[2]
    if (cache.dtype != jnp.bfloat16 or lanes != LATENT_LANES
            or rank % (2 * lanes) or n_rows % 2):
        raise ValueError(
            f"{name} reads bf16 pages of 128 lanes a row and "
            f"an even number of rows; got {cache.dtype} {cache.shape}"
            + (f", latent rank {rank}" if rank else "")
        )


def _halves(words):
    """[n, 128] uint32 of packed bf16 pairs -> (even row, odd row) as bf16."""
    lo = pltpu.bitcast(words << 16, jnp.float32)
    hi = pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _kernel(*refs, bs: int, chunk: int, lat_rows: int, scale: float,
            n_staged: int):
    # scalar prefetch (SMEM): counts [Tq] selected tokens of each query and,
    # where pages are staged, table [mb] the chunk row's pages
    counts_ref, *refs = refs
    if n_staged:
        table_ref, *refs = refs
    (
        tok_ref,    # SMEM [1, 1, K] this query's token rows (selected first)
        qc_ref,     # VMEM [1, R, h, 128] the absorbed query, 128 lanes a row
        qp_ref,     # VMEM [1, h, 128] [q_pe | 0]
        k_hbm,      # ANY/HBM [nb * bs, rows, 128] the latent
        v_hbm,      # ANY/HBM [nb * bs, rows / 2, 2, 128]; [t, 0, 0] = k_pe
        o_ref,      # VMEM [1, R, h, 128]
        k_buf,      # VMEM [2, C, rows, 128] bf16
        v_buf,      # VMEM [2, C, 2, 128] bf16
        sem,        # DMA sems [2 (k / v), 2 (slot)]
        *staged,    # VMEM [mb * bs, rows, 128], [mb * bs, 2, 128], sems [2]
    ) = refs
    t = pl.program_id(0)
    n = counts_ref[t]
    n_chunks = (n + chunk - 1) // chunk
    h = qp_ref.shape[1]

    def each_group(c, fn):
        """``fn(g)`` for the groups of ``UNROLL`` tokens of chunk ``c`` that
        hold a selected one (the scalar unit issues one copy after the
        other, and a short body's loop overhead is a fifth of the launch)."""
        left = jnp.minimum(n - c * chunk, chunk)

        def body(g, carry):
            fn(g)
            return carry

        jax.lax.fori_loop(0, (left + UNROLL - 1) // UNROLL, body, 0)

    def fill(c, put):
        """``put(token row, j)`` for the tokens of chunk ``c``, whole groups:
        a tail's last group runs into padding, which is a real token row."""
        def group(g):
            for i in range(UNROLL):
                j = g * UNROLL + i
                put(tok_ref[0, 0, c * chunk + j], j)

        each_group(c, group)

    def start_copies(c, slot):
        def put(tok, j):
            pltpu.make_async_copy(
                k_hbm.at[tok], k_buf.at[slot, j], sem.at[0, slot]).start()
            pltpu.make_async_copy(
                v_hbm.at[tok, 0], v_buf.at[slot, j], sem.at[1, slot]).start()

        fill(c, put)

    def wait_copies(c, slot):
        def landed(first, size):
            # never started: the descriptors say how many bytes to wait for
            for a, buf in enumerate((k_buf, v_buf)):
                dst = buf.at[slot, pl.ds(first, size)]
                pltpu.make_async_copy(dst, dst, sem.at[a, slot]).wait()

        whole = n - c * chunk >= chunk

        @pl.when(whole)
        def _chunk():
            landed(0, chunk)

        @pl.when(jnp.logical_not(whole))
        def _tail():
            each_group(c, lambda g: landed(g * UNROLL, UNROLL))

    if n_staged:
        k_res, v_res, res_sem = staged

        @pl.when(t == 0)
        def _stage():
            def page(p):
                src = pl.ds(table_ref[p] * bs, bs)
                dst = pl.ds(p * bs, bs)
                return (
                    pltpu.make_async_copy(
                        k_hbm.at[src], k_res.at[dst], res_sem.at[0]),
                    pltpu.make_async_copy(
                        v_hbm.at[src, 0], v_res.at[dst], res_sem.at[1]),
                )

            def every_page(op):
                def body(p, carry):
                    for copy in page(p):
                        op(copy)
                    return carry

                jax.lax.fori_loop(0, table_ref.shape[0], body, 0)

            every_page(lambda copy: copy.start())
            every_page(lambda copy: copy.wait())

        def pick(c, slot):
            def put(pos, j):
                k_buf[slot, j] = k_res[pos]
                v_buf[slot, j] = v_res[pos]

            fill(c, put)

        def start(c, slot):
            pl.when(t < n_staged)(lambda: pick(c, slot))
            pl.when(t >= n_staged)(lambda: start_copies(c, slot))

        def wait(c, slot):
            pl.when(t >= n_staged)(lambda: wait_copies(c, slot))
    else:
        start, wait = start_copies, wait_copies

    @pl.when(t == 0)
    def _clean():
        # a masked key's weight is an exact 0, and 0 * NaN = NaN: a row of the
        # buffer holds zeros until it holds a token (a tail leaves rows of
        # earlier chunks behind it), never what VMEM held before the launch
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)

    @pl.when(n > 0)
    def _first():
        start(0, 0)

    k_words = k_buf.bitcast(jnp.uint32)     # [2, C, rows / 2, 128]
    v_words = v_buf.bitcast(jnp.uint32)     # [2, C, 1, 128]

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(c + 1, 1 - slot)

        wait(c, slot)
        left = n - c * chunk                       # real tokens of this chunk
        lat = []
        for w in range(lat_rows // 2):
            lat.extend(_halves(k_words[slot, :, w, :]))
        pe, _ = _halves(v_words[slot, :, 0, :])
        s = jax.lax.dot_general(
            qp_ref[0], pe, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for r in range(lat_rows):
            s += jax.lax.dot_general(
                qc_ref[0, r], lat[r], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        col = jax.lax.broadcasted_iota(jnp.int32, (h, chunk), 1)
        s = jnp.where(col < left, s * scale, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pb = p.astype(jnp.bfloat16)
        acc = tuple(
            alpha * a + jnp.dot(pb, lat[r], preferred_element_type=jnp.float32)
            for r, a in enumerate(acc)
        )
        return m_new, l, acc

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = tuple(
        jnp.zeros((h, LATENT_LANES), jnp.float32) for _ in range(lat_rows)
    )
    _, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
    inv = 1.0 / jnp.where(l > 0, l, 1.0)
    for r in range(lat_rows):
        o_ref[0, r] = (acc[r] * inv).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "n_chunk", "interpret")
)
def sparse_latent_attention(
    q: jax.Array,            # [Tq, h, rank + 128]: [absorbed q | q_pe | 0]
    k_cache: jax.Array,      # [nb, bs, rows, 128] bf16
    v_cache: jax.Array,      # [nb, bs, rows, 128] bf16
    tables: jax.Array,       # [R, mb] int32
    rows: jax.Array,         # [Tq] the table of each query
    sel: jax.Array,          # [Tq, K] selected positions FIRST, SEL_NONE after
    *, scale: float, n_chunk: int = 0, interpret: bool = False,
) -> jax.Array:
    """ops/attention.sparse_latent_attention has the contract; returns
    [Tq, h, rank]. The first ``n_chunk`` queries share ``rows[0]`` (one
    row's chunk): its pages are staged in VMEM once where they fit."""
    Tq, h, width = q.shape
    nb, bs, n_rows, lanes = k_cache.shape
    mb = tables.shape[1]
    rank = width - lanes
    lat_rows = rank // lanes
    _check_rows_pool("sparse_latent_attention", k_cache, rank)
    _check_rows_pool("sparse_latent_attention", v_cache)
    K = sel.shape[1]
    chunk = min(CHUNK, -(-K // UNROLL) * UNROLL)
    pad = (-K) % chunk
    if pad:
        sel = jnp.pad(sel, ((0, 0), (0, pad)), constant_values=-1)
    counts = jnp.sum(sel >= 0, axis=1).astype(jnp.int32)
    tok = selected_token_rows(tables, rows, sel, bs).astype(jnp.int32)
    staged_bytes = mb * bs * (n_rows + 2) * lanes * k_cache.dtype.itemsize
    n_staged = n_chunk if staged_bytes <= STAGED_VMEM_BYTES else 0
    prefetch = [counts]
    scratch = [
        pltpu.VMEM((2, chunk, n_rows, lanes), k_cache.dtype),
        pltpu.VMEM((2, chunk, 2, lanes), v_cache.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    if n_staged:
        # a chunk query's token rows are positions in the staged copy
        staged = jnp.arange(Tq)[:, None] < n_staged
        tok = jnp.where(staged, jnp.maximum(sel, 0), tok)
        prefetch.append(tables[rows[0]].astype(jnp.int32))
        scratch += [
            pltpu.VMEM((mb * bs, n_rows, lanes), k_cache.dtype),
            pltpu.VMEM((mb * bs, 2, lanes), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    qc = q[..., :rank].reshape(Tq, h, lat_rows, lanes).transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(
            _kernel, bs=bs, chunk=chunk, lat_rows=lat_rows, scale=scale,
            n_staged=n_staged,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(Tq,),
            in_specs=[
                pl.BlockSpec((1, 1, K + pad), lambda t, *_: (t, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, lat_rows, h, lanes),
                             lambda t, *_: (t, 0, 0, 0)),
                pl.BlockSpec((1, h, lanes), lambda t, *_: (t, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, lat_rows, h, lanes), lambda t, *_: (t, 0, 0, 0)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((Tq, lat_rows, h, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the staged pages persist across the grid's programs, in order
            dimension_semantics=("arbitrary",),
            # the staged pages plus the default's room for everything else
            vmem_limit_bytes=(
                staged_bytes + 16 * 1024 * 1024 if n_staged else None
            ),
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        *prefetch, tok[:, None, :], qc, q[..., rank:],
        k_cache.reshape(nb * bs, n_rows, lanes),
        v_cache.reshape(nb * bs, v_cache.shape[2] // 2, 2, lanes),
    )
    return out.transpose(0, 2, 1, 3).reshape(Tq, h, rank)


def index_chunk_pages(tables: jax.Array) -> int:
    """Pages a chunk of ``paged_index_keys`` over these tables."""
    return min(INDEX_CHUNK_PAGES, tables.shape[1])


def index_chunk_reads(tables: jax.Array) -> tuple[jax.Array, jax.Array]:
    """What ``paged_index_keys`` reads of these tables by the chunk: the
    whole chunks (every table is read to its last page: the launch takes no
    context length) and those of them read as runs of consecutive pages."""
    runs = chunk_runs(tables, index_chunk_pages(tables))
    return jnp.asarray(runs.size, jnp.int32), jnp.sum(runs)


def _index_keys_kernel(tables_ref, runs_ref, v_hbm, o_ref, buf, sem, *,
                       bs: int, cp: int, mb: int):
    # scalar prefetch (SMEM): tables [R * mb], runs [R * (mb // cp)]
    # v_hbm  ANY/HBM [nb * bs, rows / 2, 2, 128]; [t, 0] = [k_pe | index key]
    # o_ref  VMEM [1, T, 128] bf16: chunk c of table r
    # buf    VMEM [2, T, 2, 128] bf16; sem DMA [2 (slot)]
    T = cp * bs
    n_whole, tail = mb // cp, mb % cp
    n_chunks = n_whole + (1 if tail else 0)
    r, c = pl.program_id(0), pl.program_id(1)
    i = r * n_chunks + c
    slot = jax.lax.rem(i, 2)

    def whole_or_tail(c, whole, tail_chunk):
        """A table's chunks are whole but its last, where ``mb`` leaves a
        tail: static sizes either way, one branch on ``c``."""
        if not tail:
            whole()
        else:
            pl.when(c < n_whole)(whole)
            pl.when(c == n_whole)(tail_chunk)

    def page(r, c, j, slot):
        src = pl.ds(tables_ref[r * mb + c * cp + j] * bs, bs)
        return pltpu.make_async_copy(
            v_hbm.at[src, 0], buf.at[slot, pl.ds(j * bs, bs)], sem.at[slot])

    def pages(r, c, slot, n: int):
        unroll = INDEX_UNROLL if n % INDEX_UNROLL == 0 else 1

        def group(g, carry):
            for u in range(unroll):
                page(r, c, g * unroll + u, slot).start()
            return carry

        jax.lax.fori_loop(0, n // unroll, group, 0)

    def start(r, c, slot):
        def whole():
            run = runs_ref[r * n_whole + c] != 0

            @pl.when(run)
            def _run():
                first = tables_ref[r * mb + c * cp] * bs
                pltpu.make_async_copy(
                    v_hbm.at[pl.ds(pl.multiple_of(first, bs), T), 0],
                    buf.at[slot], sem.at[slot]).start()

            pl.when(jnp.logical_not(run))(lambda: pages(r, c, slot, cp))

        whole_or_tail(c, whole, lambda: pages(r, c, slot, tail))

    def wait(c, slot):
        def landed(n_tokens: int):
            # never started: the descriptor says how many bytes to wait for,
            # the same however the chunk was started
            dst = buf.at[slot, pl.ds(0, n_tokens)]
            pltpu.make_async_copy(dst, dst, sem.at[slot]).wait()

        whole_or_tail(c, lambda: landed(T), lambda: landed(tail * bs))

    @pl.when(i == 0)
    def _first():
        start(r, c, slot)

    @pl.when(i + 1 < pl.num_programs(0) * n_chunks)
    def _next():
        last = c + 1 == n_chunks
        start(jnp.where(last, r + 1, r), jnp.where(last, 0, c + 1), 1 - slot)

    wait(c, slot)
    # both slots as word-rows, a token each: its key the high halves
    words = buf.bitcast(jnp.uint32).reshape(2 * T, LATENT_LANES)
    for t0 in range(0, T, INDEX_UNPACK_TOKENS):
        n = min(INDEX_UNPACK_TOKENS, T - t0) // 2
        _, keys = _pack_pairs(
            words[pl.ds(slot * T + t0, n, stride=2), :],
            words[pl.ds(slot * T + t0 + 1, n, stride=2), :],
        )
        # the words of 2n tokens' keys, handed on as a bf16 VALUE: Mosaic
        # re-tiles the registers for it (8 instructions a register where a
        # uint32 buffer copied out by hand has none), under the copies all
        # the same: 0.144 against 0.147 ms a decode launch on the chip
        o_ref[0, pl.ds(t0, 2 * n), :] = pltpu.bitcast(keys, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dim", "interpret"))
def paged_index_keys(
    v_cache: jax.Array,      # [nb, bs, rows, 128] bf16, row 1 = [kI | 0]
    tables: jax.Array,       # [R, mb] int32
    dim: int,
    *, interpret: bool = False,
) -> jax.Array:
    """ops/attention.paged_index_keys has the contract and is the twin: the
    index keys (their first ``dim`` lanes) of each table's context, ``[R, mb *
    bs, dim]``, the same bits."""
    nb, bs, n_rows, lanes = v_cache.shape
    R, mb = tables.shape
    _check_rows_pool("paged_index_keys", v_cache)
    if bs % 2 or dim > lanes:
        raise ValueError(
            f"paged_index_keys packs pairs of tokens of a page and reads at "
            f"most {lanes} lanes a key; got pages of {bs}, dim {dim}"
        )
    cp = index_chunk_pages(tables)
    T = cp * bs
    tables = tables.astype(jnp.int32)
    keys = pl.pallas_call(
        functools.partial(_index_keys_kernel, bs=bs, cp=cp, mb=mb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, -(-mb // cp)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, T, lanes), lambda r, c, *_: (r, c, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, T, 2, lanes), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, mb * bs, lanes), v_cache.dtype),
        compiler_params=pltpu.CompilerParams(
            # a program starts the next one's chunk: in order
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=INDEX_KERNEL_NAME,
    )(
        tables.reshape(-1), chunk_runs(tables, cp).reshape(-1).astype(jnp.int32),
        v_cache.reshape(nb * bs, n_rows // 2, 2, lanes),
    )
    return keys if dim == lanes else keys[..., :dim]
