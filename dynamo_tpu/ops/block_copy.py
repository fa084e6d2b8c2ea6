"""Pallas TPU kernels: batched KV block gather / scatter / copy.

TPU-native analog of the reference's CUDA batched block-copy kernel
(lib/llm/src/kernels/block_copy.cu, ``copy_blocks_kernel`` :41), which moves
paged-KV blocks between layouts for KVBM offload/onboard. Here the moves are
expressed as explicit HBM<->HBM DMAs driven by scalar-prefetched index lists —
no VMEM round-trip, no materialized gather indices, and the batch of copies
runs as overlapping async DMAs.

Meant for: gathering sealed blocks into a contiguous staging buffer for the
transfer plane, onboarding host/disk blocks back into device pages, and
allocator defragmentation (copy_blocks). The engine does not dispatch them
today — it moves pages with the XLA forms below (``cache[ids]`` /
``.at[ids].set``, the ``*_ref`` functions). The kernels compile for v5e
(tests/test_tpu_compile.py) and match the XLA forms on the chip
(chip_smoke.py) at float and int8 page shapes; a ``[num_blocks, kvh]`` scale
array does not (its row slice is not aligned to the 128-lane tiling), so
the quantized wrappers move scale rows with the XLA form always.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(ids_ref, cache_hbm, out_hbm, sem):
    """grid=(M,): DMA cache[ids[m]] -> out[m], HBM->HBM."""
    m = pl.program_id(0)
    dma = pltpu.make_async_copy(
        cache_hbm.at[ids_ref[m]], out_hbm.at[m], sem
    )
    dma.start()
    dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(
    cache: jax.Array,      # [num_blocks, bs, kvh, d] (or [num_blocks, ...])
    block_ids: jax.Array,  # [M] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Gather pages ``cache[block_ids]`` into a contiguous [M, ...] buffer."""
    M = block_ids.shape[0]
    out_shape = (M,) + cache.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, cache.dtype),
        interpret=interpret,
        name="gather_blocks",
    )(block_ids.astype(jnp.int32), cache)


def _scatter_kernel(ids_ref, blocks_hbm, cache_io, sem):
    """grid=(M,): DMA blocks[m] -> cache[ids[m]] in place (aliased)."""
    m = pl.program_id(0)
    dma = pltpu.make_async_copy(
        blocks_hbm.at[m], cache_io.at[ids_ref[m]], sem
    )
    dma.start()
    dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_blocks(
    cache: jax.Array,      # [num_blocks, ...] donated, updated in place
    block_ids: jax.Array,  # [M] int32 destination pages
    blocks: jax.Array,     # [M, ...] source pages
    *,
    interpret: bool = False,
) -> jax.Array:
    """Scatter contiguous pages into ``cache[block_ids]``; returns the updated
    cache (same buffer — input is donated/aliased)."""
    M = block_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # blocks
            pl.BlockSpec(memory_space=pl.ANY),  # cache (aliased to out)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )

    def kernel(ids_ref, blocks_hbm, cache_in, cache_io, sem):
        del cache_in  # aliased with cache_io
        _scatter_kernel(ids_ref, blocks_hbm, cache_io, sem)

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},  # cache (after 1 scalar-prefetch arg + blocks)
        interpret=interpret,
        name="scatter_blocks",
    )(block_ids.astype(jnp.int32), blocks, cache)


def _copy_kernel(src_ref, dst_ref, cache_in, cache_io, sem):
    """grid=(M,): DMA cache[src[m]] -> cache[dst[m]] in place."""
    del cache_in
    m = pl.program_id(0)
    dma = pltpu.make_async_copy(
        cache_io.at[src_ref[m]], cache_io.at[dst_ref[m]], sem
    )
    dma.start()
    dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def copy_blocks(
    cache: jax.Array,    # [num_blocks, ...] donated
    src_ids: jax.Array,  # [M] int32
    dst_ids: jax.Array,  # [M] int32 (disjoint from src_ids)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Batched page copy within one cache (defrag / prefix fork)."""
    M = src_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="copy_blocks",
    )(src_ids.astype(jnp.int32), dst_ids.astype(jnp.int32), cache)


# -- pure-JAX fallbacks (CPU / non-TPU backends) -----------------------------
def gather_blocks_ref(cache: jax.Array, block_ids: jax.Array) -> jax.Array:
    return cache[block_ids]


def scatter_blocks_ref(
    cache: jax.Array, block_ids: jax.Array, blocks: jax.Array
) -> jax.Array:
    return cache.at[block_ids].set(blocks)


def copy_blocks_ref(
    cache: jax.Array, src_ids: jax.Array, dst_ids: jax.Array
) -> jax.Array:
    return cache.at[dst_ids].set(cache[src_ids])


# -- quantized caches (ops/quant.QuantizedKV) --------------------------------
# A quantized page move is two moves — the int8 payload and its f32 scale
# row — that MUST travel together (a payload under the wrong scale is silent
# corruption, not an error). These wrappers keep the pair atomic for the
# KVBM offload/onboard and transfer staging paths. ``interpret=True`` runs
# the payload through the DMA kernels in the Pallas interpreter (tests);
# otherwise both halves take the XLA forms, like the engine's float pages.
def gather_blocks_quant(cache, block_ids: jax.Array, *, interpret: bool = False):
    """QuantizedKV pages -> (payload [M, bs, kvh, d] int8, scales [M, kvh])."""
    from .quant import QuantizedKV

    payload = (
        gather_blocks(cache.data, block_ids, interpret=True) if interpret
        else gather_blocks_ref(cache.data, block_ids)
    )
    return QuantizedKV(payload, gather_blocks_ref(cache.scale, block_ids))


def scatter_blocks_quant(
    cache, block_ids: jax.Array, blocks, *, interpret: bool = False
):
    """Scatter (payload, scales) pages into a QuantizedKV cache."""
    from .quant import QuantizedKV

    payload = (
        scatter_blocks(cache.data, block_ids, blocks.data, interpret=True)
        if interpret
        else scatter_blocks_ref(cache.data, block_ids, blocks.data)
    )
    return QuantizedKV(
        payload, scatter_blocks_ref(cache.scale, block_ids, blocks.scale)
    )
