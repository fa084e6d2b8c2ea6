"""Pallas TPU kernel: flash prefix-extend attention for chunked prefill.

Drop-in replacement for ``ops.attention.extend_attention`` on the prefill hot
path. The pure-JAX formulation materializes the full [S, h, T] score tensor
(67 MB of f32 per head at an 8k context) and re-reads it for softmax and PV;
this kernel streams KV tiles through VMEM with online-softmax accumulation —
O(tile) VMEM at any context length, the standard flash-attention recipe
tiled for the MXU.

The TPU analog of the prefill-side flash kernels the reference's engines use
internally (vLLM/TRT-LLM chunked-prefill attention; SURVEY §2.5). Shares the
contiguous gathered-KV layout of ops/attention.py: the engine gathers pages
once per chunk, and this kernel replaces only the attention math.

Grid: (kv_heads, q_tiles, kv_tiles) — the LAST dim iterates sequentially on
TPU, so the online-softmax state (m/l/acc) lives in VMEM scratch carried
across kv steps; K/V arrive one (kv_tile, d) block at a time via BlockSpecs.
Tiles entirely past this q-tile's attention limit skip their matmuls
(``pl.when``). Causality is absolute-position based (``q_positions`` vs key
index), so the same kernel serves first-chunk prefill, chunked continuation
against a cached prefix, and prefix-cache-reuse suffixes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import shard_map

NEG_INF = -1e30

# default tile sizes; the engine's eligibility guard imports these so the
# two never drift (engine/engine.py prefill attend)
Q_TILE = 128
KV_TILE = 256


def _prefill_kernel(
    start_ref,   # SMEM [1] int32 absolute position of q row 0 (scalar prefetch)
    tlen_ref,    # SMEM [1] int32 valid context length (scalar prefetch)
    q_ref,       # VMEM [1, TQ, g, d] this (kv_head, q_tile)'s queries
    k_ref,       # VMEM [1, KT, d] one KV tile of this kv_head's context
    v_ref,       # VMEM [1, KT, d]
    # quantized=True only: ks_ref/vs_ref VMEM [1, KT, 1] f32 per-position
    # scales (per-block scales broadcast at gather time)
    *rest,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    # Mosaic only loads SCALARS from SMEM, so q positions can't arrive as a
    # prefetched vector; they're derived from start_ref + the row iota
    # instead (engine chunks are contiguous — _chunk_arrays). Both the
    # per-row mask and the tile-skip bound are then scalar-rooted.
    qt = pl.program_id(1)
    c = pl.program_id(2)
    n_kv = pl.num_programs(2)
    _, TQ, g, d = q_ref.shape
    KT = k_ref.shape[1]
    start = start_ref[0]
    tlen = tlen_ref[0]

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # per-row attention limit: keys at index < min(q_pos+1, total_len);
    # rows past the real chunk clamp to tlen (their output is discarded)
    tile_hi = jnp.minimum(start + (qt + 1) * TQ, tlen)         # scalar

    @pl.when(c * KT < tile_hi)
    def _tile():
        scale = 1.0 / (d ** 0.5)
        q2 = (q_ref[0].astype(jnp.float32) * scale).reshape(TQ * g, d)
        # row index per flattened (q, g) pair, built directly in the
        # [TQ*g, 1] layout: reshaping a (TQ, g) iota would shape-cast across
        # the lane dim, which Mosaic rejects (infer-vector-layout error on
        # real TPU); iota//g keeps the lane dim fixed at 1 throughout
        row = jax.lax.broadcasted_iota(jnp.int32, (TQ * g, 1), 0) // g
        pos = start + qt * TQ + row
        lim2 = jnp.minimum(pos + 1, tlen)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequantize in-register: [KT, d] int8 tile * [KT, 1] scale
            # column (lane-dim broadcast) — the HBM->VMEM tile stream stays
            # int8, so prefill context reads halve vs bf16 too
            k = k * ks_ref[0]
            v = v * vs_ref[0]
        s = jax.lax.dot_general(
            q2, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [TQ*g, KT]
        key_pos = c * KT + jax.lax.broadcasted_iota(jnp.int32, (1, KT), 1)
        s = jnp.where(key_pos < lim2, s, NEG_INF)

        m_prev, l_prev, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(c == n_kv - 1)
    def _emit():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.reshape(TQ, g, d).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("q_tile", "kv_tile", "interpret")
)
def flash_extend_attention(
    q: jax.Array,            # [S, h, d] new-chunk queries
    k_ctx: jax.Array,        # [T, kvh, d] gathered context (padded)
    v_ctx: jax.Array,
    q_positions: jax.Array,  # [S] absolute positions
    total_len: jax.Array,    # scalar valid context length
    *,
    k_scales: jax.Array = None,  # [T, kvh] f32: k_ctx/v_ctx are int8 pages
    v_scales: jax.Array = None,  # (ops.attention.gather_kv_quant output)
    q_tile: int = Q_TILE,
    kv_tile: int = KV_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Same semantics as ``ops.attention.extend_attention`` for CONTIGUOUS
    q_positions (the engine's chunks are: row i sits at q_positions[0]+i;
    padded tail rows may carry arbitrary positions — their output is
    discarded by the caller). S and T must be multiples of the tile sizes
    (the engine's bucketed chunks are).

    With ``k_scales``/``v_scales`` the context is int8 (quantized paged
    cache) and the kernel dequantizes each tile in-register."""
    S, h, d = q.shape
    T, kvh, _ = k_ctx.shape
    g = h // kvh
    quantized = k_scales is not None
    if S % q_tile or T % kv_tile:
        raise ValueError(
            f"S={S} / T={T} not multiples of tiles ({q_tile}, {kv_tile})"
        )
    nq = S // q_tile
    nkv = T // kv_tile

    # [S, h, d] -> [kvh, S, g, d]: each kv head's q group contiguous
    qg = q.reshape(S, kvh, g, d).transpose(1, 0, 2, 3)
    kg = k_ctx.transpose(1, 0, 2)  # [kvh, T, d]
    vg = v_ctx.transpose(1, 0, 2)

    in_specs = [
        pl.BlockSpec((1, q_tile, g, d), lambda kh, qt, c, *_: (kh, qt, 0, 0)),
        pl.BlockSpec((1, kv_tile, d), lambda kh, qt, c, *_: (kh, c, 0)),
        pl.BlockSpec((1, kv_tile, d), lambda kh, qt, c, *_: (kh, c, 0)),
    ]
    args = [qg, kg, vg]
    if quantized:
        # [T, kvh] -> [kvh, T, 1]: tiles broadcast over the lane (d) dim
        in_specs += [
            pl.BlockSpec((1, kv_tile, 1), lambda kh, qt, c, *_: (kh, c, 0)),
            pl.BlockSpec((1, kv_tile, 1), lambda kh, qt, c, *_: (kh, c, 0)),
        ]
        args += [
            k_scales.astype(jnp.float32).T[:, :, None],
            v_scales.astype(jnp.float32).T[:, :, None],
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(kvh, nq, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, q_tile, g, d), lambda kh, qt, c, *_: (kh, qt, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((q_tile * g, 1), jnp.float32),
            pltpu.VMEM((q_tile * g, 1), jnp.float32),
            pltpu.VMEM((q_tile * g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, S, g, d), q.dtype),
        interpret=interpret,
        name="flash_extend_attention",
    )(
        q_positions[:1].astype(jnp.int32),  # chunk start (row 0's position)
        jnp.asarray(total_len, jnp.int32).reshape(1),
        *args,
    )
    # [kvh, S, g, d] -> [S, h, d]
    return out.transpose(1, 0, 2, 3).reshape(S, h, d)


def sharded_flash_extend_attention(
    mesh: Mesh,
    tp_axis: str,
    q: jax.Array,
    k_ctx: jax.Array,
    v_ctx: jax.Array,
    q_positions: jax.Array,
    total_len: jax.Array,
    k_scales: jax.Array = None,
    v_scales: jax.Array = None,
    **kw,
) -> jax.Array:
    """TP-sharded wrapper: extend attention is head-wise independent, so each
    TP shard runs the kernel on its own heads (q sharded on h, context on
    kvh). shard_map because GSPMD cannot partition a custom call — the same
    treatment as pallas_attention.sharded_paged_decode_attention."""
    if mesh.shape[tp_axis] == 1:
        return flash_extend_attention(
            q, k_ctx, v_ctx, q_positions, total_len,
            k_scales=k_scales, v_scales=v_scales, **kw
        )
    in_specs = [
        P(None, tp_axis, None),
        P(None, tp_axis, None),
        P(None, tp_axis, None),
        P(None),
        P(),
    ]
    args = [q, k_ctx, v_ctx, q_positions, total_len]
    if k_scales is not None:
        # int8 context: scale rows shard on their kv-head dim with the pages
        in_specs += [P(None, tp_axis), P(None, tp_axis)]
        args += [k_scales, v_scales]

    def body(q_, k_, v_, pos_, tlen_, *scales_):
        ks_, vs_ = scales_ if scales_ else (None, None)
        return flash_extend_attention(
            q_, k_, v_, pos_, tlen_, k_scales=ks_, v_scales=vs_, **kw
        )

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, tp_axis, None),
        check_vma=False,
    )
    return fn(*args)
