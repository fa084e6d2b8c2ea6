"""Pallas TPU kernel: latent attention over the token rows a query selected.

Learned sparse attention (models/mla.py, DSA) gives every query its own list
of at most ``index_topk`` absolute positions. Neither page-contiguous kernel
computes that: this one copies the selected TOKENS, one by one, through the
block table, and runs the absorbed heads as MQA over them with an online
softmax. A token's latent is read once: the values are the same rows as the
keys (``W_uv`` is applied past the softmax by the model).

Layout (ops/attention.py has the twin and the layout's description): both
paged arrays are ``[num_blocks, block_size, rows, 128]`` in bf16, so a token
of either is whole ``(2, 128)`` tiles of packed pairs of rows and can be the
source of a copy of its own; 576 lanes (512 + 64) cannot: Mosaic slices HBM
by whole tiles. In VMEM a pair of rows shares a 32-bit word (row ``2w`` the
low half, ``2w + 1`` the high half), so the buffers are read as ``uint32``
and each half becomes one ``[tokens, 128]`` bf16 matrix by a shift or a mask:
``rows`` matrices of the latent and one of ``k_pe`` (the index key beside it
in the word is dropped).

Grid: one program a query. A query's selected rows are walked in chunks of
``CHUNK`` tokens in two slots: while chunk ``c`` is computed, chunk ``c + 1``
is being copied. The flat token rows (``block * block_size + offset``,
computed by the launch from the block tables) arrive in SMEM a query at a
time; the number selected is scalar-prefetched and a query with none copies
nothing and returns zeros. One DMA semaphore a slot and array; every token's
copy signals it and it is waited once a token.

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
CHUNK = 256   # selected tokens a chunk: 2 slots x (rows + rows) x 256 x 256 B
# copies issued (and waited for) a pass of the loop over a whole chunk: on a
# v5e a 512 + 8 query launch over 2 048 keys runs 81.6 / 72.2 / 67.1 ms at
# 1 / 4 / 16 (PERF.md section 6, PR 31)
UNROLL = 16


def _halves(words):
    """[n, 128] uint32 of packed bf16 pairs -> (even row, odd row) as bf16."""
    lo = pltpu.bitcast(words << 16, jnp.float32)
    hi = pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _kernel(
    # scalar prefetch (SMEM)
    counts_ref,     # [Tq] int32 selected tokens of each query
    # inputs
    tok_ref,        # SMEM [1, 1, K] this query's token rows (selected first)
    qc_ref,         # VMEM [1, R, h, 128] the absorbed query, 128 lanes a row
    qp_ref,         # VMEM [1, h, 128] [q_pe | 0]
    k_hbm,          # ANY/HBM [nb, bs, rows, 128] the latent
    v_hbm,          # ANY/HBM [nb, bs, rows, 128] row 0 = [k_pe | 0]
    # outputs
    o_ref,          # VMEM [1, R, h, 128]
    # scratch
    k_buf,          # VMEM [2, C, rows, 128] bf16
    v_buf,          # VMEM [2, C, rows, 128] bf16
    sem,            # DMA sems [2 (k / v), 2 (slot)]
    *, bs: int, chunk: int, lat_rows: int, scale: float,
):
    n = counts_ref[pl.program_id(0)]
    n_chunks = (n + chunk - 1) // chunk
    h = qp_ref.shape[1]

    def copies(tok, slot, j):
        blk, off = tok // bs, tok % bs
        return (
            pltpu.make_async_copy(
                k_hbm.at[blk, off], k_buf.at[slot, j], sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[blk, off], v_buf.at[slot, j], sem.at[1, slot]),
        )

    def each_token(c, fn):
        """``fn(j)`` for the tokens of chunk ``c``; a whole chunk ``UNROLL``
        tokens a pass (the scalar unit issues one copy after the other, and
        a short body's loop overhead is a fifth of the launch)."""
        left = n - c * chunk
        unroll = UNROLL if chunk % UNROLL == 0 else 1

        def body(j, carry):
            fn(j)
            return carry

        def group(g, carry):
            for i in range(unroll):
                fn(g * unroll + i)
            return carry

        @pl.when(left >= chunk)
        def _whole():
            jax.lax.fori_loop(0, chunk // unroll, group, 0)

        @pl.when(left < chunk)
        def _tail():
            jax.lax.fori_loop(0, left, body, 0)

    def start(c, slot):
        def issue(j):
            for copy in copies(tok_ref[0, 0, c * chunk + j], slot, j):
                copy.start()

        each_token(c, issue)

    def wait(c, slot):
        def one(j):
            # the descriptor only says how many bytes one token signals
            for copy in copies(0, slot, 0):
                copy.wait()

        each_token(c, one)

    @pl.when(n > 0)
    def _first():
        start(0, 0)

    k_words = k_buf.bitcast(jnp.uint32)     # [2, C, rows / 2, 128]
    v_words = v_buf.bitcast(jnp.uint32)

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
        # never-copied rows of the buffer may hold NaN, and 0 * NaN = NaN
        real = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < left
        pb = p.astype(jnp.bfloat16)
        acc = tuple(
            alpha * a + jnp.dot(
                pb, jnp.where(real, lat[r], 0),
                preferred_element_type=jnp.float32,
            )
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


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def sparse_latent_attention(
    q: jax.Array,            # [Tq, h, rank + 128]: [absorbed q | q_pe | 0]
    k_cache: jax.Array,      # [nb, bs, rows, 128] bf16
    v_cache: jax.Array,      # [nb, bs, rows, 128] bf16
    tables: jax.Array,       # [R, mb] int32
    rows: jax.Array,         # [Tq] the table of each query
    sel: jax.Array,          # [Tq, K] selected positions FIRST, SEL_NONE after
    *, scale: float, interpret: bool = False,
) -> jax.Array:
    """ops/attention.sparse_latent_attention has the contract; returns
    [Tq, h, rank]."""
    Tq, h, width = q.shape
    nb, bs, n_rows, lanes = k_cache.shape
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
    chunk = min(CHUNK, -(-K // 16) * 16)
    pad = (-K) % chunk
    if pad:
        sel = jnp.pad(sel, ((0, 0), (0, pad)), constant_values=-1)
    counts = jnp.sum(sel >= 0, axis=1).astype(jnp.int32)
    tok = selected_token_rows(tables, rows, sel, bs).astype(jnp.int32)
    qc = q[..., :rank].reshape(Tq, h, lat_rows, lanes).transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(
            _kernel, bs=bs, chunk=chunk, lat_rows=lat_rows, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Tq,),
            in_specs=[
                pl.BlockSpec((1, 1, K + pad), lambda t, c: (t, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, lat_rows, h, lanes), lambda t, c: (t, 0, 0, 0)),
                pl.BlockSpec((1, h, lanes), lambda t, c: (t, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, lat_rows, h, lanes), lambda t, c: (t, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, n_rows, lanes), k_cache.dtype),
                pltpu.VMEM((2, chunk, n_rows, lanes), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Tq, lat_rows, h, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(counts, tok[:, None, :], qc, q[..., rank:], k_cache, v_cache)
    return out.transpose(0, 2, 1, 3).reshape(Tq, h, rank)
