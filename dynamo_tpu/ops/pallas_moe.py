"""Pallas TPU kernel: grouped matrix multiplication over token-sorted rows.

The one-chip expert layer (models/moe.py ``moe_ffn_grouped``) sorts its
``T * K`` (token, expert) assignments by expert, so expert ``e`` owns the
contiguous rows ``[offsets[e], offsets[e + 1])``. One launch multiplies every
row block by ITS expert's matrix: an expert nobody was routed to is never
read, and a row is multiplied by one expert only — the bytes and FLOPs the
routing needs, not ``E`` times them (the dense oracle) nor ``T * K`` private
weight copies (the per-token gather this replaced).

The layout is the megablox one (PAPERS.md, MegaBlocks; the scheme of
``jax.experimental.pallas.ops.tpu.megablox``): rows are cut into ``tm``-row
tiles and the grid walks VISITS, one per (group, row tile) pair that
overlaps, in row order. A group that starts inside a tile visits that tile
again, so there are at most ``tiles_m + E - 1`` visits; the tables that map
a visit to its group and its row tile are scalar-prefetched, the BlockSpec
index maps read them, and a visit stores only the rows its group owns
(masked), so consecutive visits of one tile fill it between them. The grid
``(tiles_n, visits, tiles_k)`` is static at its largest, and a visit past
the real count is DEAD: it computes nothing, and its index maps
(``index_maps``) hold every block index of every operand at what the last
real step had: the group, the row tile AND the k index, which the last real
visit left at ``tiles_k - 1``. The pipeline copies a block only where its
index changes, so a launch reads each touched expert's matrix once a row
tile its rows straddle. Until PR 43 the k index walked on through the dead
visits, and each of them read the last touched expert's whole matrix again
wherever ``tiles_k > 1``. A launch with no real visit holds visit 0's
indices and reads one block a pass over ``n``.

With two right-hand sides the launch is the SwiGLU front half: both
products accumulate side by side in float32 and the epilogue stores
``silu(gate) * up`` — one read of the rows, no [rows, I] round trip.

Every launch carries the name ``moe_grouped_matmul``: the device trace and
the benchmark's roofline reader find it by that name.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "moe_grouped_matmul"
# rows per tile: one MXU pass deep; a decode batch of 16 rows x 8 experts is
# exactly one tile, which every touched expert visits once. The visits the
# static grid has beyond those (tiles_m + E - 1 less the real count) are
# dead: they hold the last real step's block indices, the k index among
# them, so they cost a grid step each and no read
ROW_TILE = 128
# all right-hand-side tiles of one grid step together (each is double
# buffered by the pipeline): 2 x 4 MiB + rows, output and accumulators stay
# under the 16 MiB a kernel may use by default
_RHS_TILE_BYTES = 4 * 1024 * 1024


def _divisor_tiles(n: int) -> Tuple[int, ...]:
    """Tile sizes for a dimension of ``n``: the whole of it, and every
    divisor that is a multiple of the 128-lane tiling."""
    return tuple(sorted({n} | {t for t in range(128, n, 128) if n % t == 0}))


def pick_tiles(k: int, n: int, itemsize: int, n_rhs: int) -> Tuple[int, int]:
    """(tk, tn) for one right-hand-side tile: the largest that fits the
    budget, whole output rows first (a [tk, n] slab of an expert's matrix is
    one contiguous read)."""
    budget = _RHS_TILE_BYTES // (itemsize * n_rhs)
    fits = [
        (tk * tn, tn, tk)
        for tk in _divisor_tiles(k) for tn in _divisor_tiles(n)
        if tk * tn <= budget
    ]
    if not fits:
        return min(_divisor_tiles(k)), min(_divisor_tiles(n))
    _, tn, tk = max(fits)
    return tk, tn


def visit_tables(group_sizes: jax.Array, m: int, tm: int):
    """The visit order of ``m`` sorted rows cut into ``tm``-row tiles:
    ``offsets`` [E + 1] (group e owns rows offsets[e]:offsets[e + 1]),
    ``group_of`` / ``tile_of`` [tiles_m + E - 1] (visit v works on that group
    and that row tile) and the number of real visits [1]."""
    E = group_sizes.shape[0]
    tiles_m = m // tm
    visits = tiles_m + E - 1
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts = offsets[:-1]
    # tiles a group overlaps: from the tile its first row is in to the tile
    # its last row is in
    group_tiles = jnp.where(
        group_sizes > 0, (ends + tm - 1) // tm - starts // tm, 0
    )
    group_of = jnp.repeat(
        jnp.arange(E, dtype=jnp.int32), group_tiles, total_repeat_length=visits
    )
    # a tile is visited once by the group that owns its first row, and once
    # more by every group that starts inside it
    starts_inside = (starts % tm != 0) & (group_sizes > 0)
    tile_visits = 1 + jnp.zeros((tiles_m,), jnp.int32).at[
        jnp.where(starts_inside, starts // tm, tiles_m)
    ].add(1, mode="drop")
    tile_of = jnp.repeat(
        jnp.arange(tiles_m, dtype=jnp.int32), tile_visits,
        total_repeat_length=visits,
    )
    n_visits = group_tiles.sum().astype(jnp.int32)
    # past the last real visit: stay on its blocks, so nothing moves
    last = jnp.maximum(n_visits - 1, 0)
    live = jnp.arange(visits) < n_visits
    group_of = jnp.where(live, group_of, group_of[last])
    tile_of = jnp.where(live, tile_of, tile_of[last])
    return offsets, group_of, tile_of, n_visits[None]


def index_maps(tiles_k: int):
    """The BlockSpec index maps ``(lhs, rhs, out)`` of the grid ``(n_i, v,
    k_i)`` over the four scalar-prefetched tables. A dead visit (``v >=
    n_visits``) returns what the step before it did: ``visit_tables`` holds
    its group and row tile at the last real visit's, and its k index is held
    here at ``tiles_k - 1``, where that visit ended; no index changes, so the
    pipeline starts no copy."""

    def held_k(v, k_i, n_visits):
        return jnp.where(v < n_visits[0], k_i, tiles_k - 1)

    def lhs_map(n_i, v, k_i, offsets, group_of, tile_of, n_visits):
        return tile_of[v], held_k(v, k_i, n_visits)

    def rhs_map(n_i, v, k_i, offsets, group_of, tile_of, n_visits):
        return group_of[v], held_k(v, k_i, n_visits), n_i

    def out_map(n_i, v, k_i, offsets, group_of, tile_of, n_visits):
        return tile_of[v], n_i

    return lhs_map, rhs_map, out_map


def _kernel(offsets_ref, group_ref, tile_ref, n_ref, lhs_ref, *rest,
            n_rhs: int, tm: int, tiles_k: int):
    rhs_refs, out_ref, acc_refs = rest[:n_rhs], rest[n_rhs], rest[n_rhs + 1:]
    v, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(v < n_ref[0])
    def _visit():
        @pl.when(k_i == 0)
        def _zero():
            for acc in acc_refs:
                acc[...] = jnp.zeros_like(acc)

        rows = lhs_ref[...]
        for rhs, acc in zip(rhs_refs, acc_refs):
            acc[...] += jax.lax.dot_general(
                rows, rhs[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(k_i == tiles_k - 1)
        def _store():
            g = group_ref[v]
            row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, 1), 0
            )
            mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
            val = acc_refs[0][...]
            if n_rhs == 2:  # SwiGLU: silu(gate) * up, in float32
                val = val * jax.nn.sigmoid(val) * acc_refs[1][...]
            out_ref[...] = jnp.where(
                mine, val.astype(out_ref.dtype), out_ref[...]
            )


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(lhs: jax.Array, rhs: Sequence[jax.Array],
                   group_sizes: jax.Array, *, interpret: bool = False
                   ) -> jax.Array:
    """``lhs`` [m, k] rows sorted by group; ``rhs`` one or two [E, k, n]
    stacks; ``group_sizes`` [E] int32 summing to at most ``m``. Returns
    [m, n] in ``lhs``'s dtype: row r of group e is ``lhs[r] @ rhs[0][e]``,
    or ``silu(lhs[r] @ rhs[0][e]) * (lhs[r] @ rhs[1][e])`` with two stacks.
    Rows past the last group's end return unspecified values."""
    rhs = tuple(rhs)
    m, k = lhs.shape
    E, _, n = rhs[0].shape
    tm = min(ROW_TILE, -(-m // 16) * 16)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tk, tn = pick_tiles(k, n, rhs[0].dtype.itemsize, len(rhs))
    tiles_k, tiles_n = k // tk, n // tn
    tables = visit_tables(group_sizes.astype(jnp.int32), m_pad, tm)
    visits = tables[1].shape[0]
    lhs_map, rhs_map, out_map = index_maps(tiles_k)

    out = pl.pallas_call(
        functools.partial(_kernel, n_rhs=len(rhs), tm=tm, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_n, visits, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map)]
            + [pl.BlockSpec((None, tk, tn), rhs_map)] * len(rhs),
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * len(rhs),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*tables, lhs, *rhs)
    return out[:m]


def grouped_matmul_reference(lhs: jax.Array, rhs: Sequence[jax.Array],
                             group_sizes: jax.Array) -> jax.Array:
    """The pure-JAX twin (``jax.lax.ragged_dot``): what the expert layer
    runs where the Pallas kernels are off, and what the kernel is held to."""
    outs = [
        jax.lax.ragged_dot(
            lhs, w, group_sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32,
        )
        for w in rhs
    ]
    val = outs[0]
    if len(outs) == 2:
        val = val * jax.nn.sigmoid(val) * outs[1]
    return val.astype(lhs.dtype)
