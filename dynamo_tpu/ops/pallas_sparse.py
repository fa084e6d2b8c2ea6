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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import LATENT_LANES, selected_token_rows
from .pallas_paged import NEG_INF

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
    if (k_cache.dtype != jnp.bfloat16 or lanes != LATENT_LANES
            or rank % (2 * lanes) or n_rows % 2):
        raise ValueError(
            "sparse_latent_attention reads bf16 pages of 128 lanes a row and "
            f"an even number of rows; got {k_cache.dtype} {k_cache.shape}, "
            f"latent rank {rank}"
        )
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
        v_cache.reshape(nb * bs, n_rows // 2, 2, lanes),
    )
    return out.transpose(0, 2, 1, 3).reshape(Tq, h, rank)
