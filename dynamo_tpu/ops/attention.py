"""Attention ops for paged-KV serving: prefill, prefix-extend, paged decode.

Pure-JAX reference implementations: the twins the Pallas kernels are held to,
and what ops/paged_attention.py (the seam the step programs ask) answers with
wherever the kernels are off.

Replaces what the reference delegates to engine-internal kernels (vLLM
paged attention / FlashInfer); the CUDA block-copy kernel analog lives in
ops/block_copy.py.

Layout: paged KV cache per layer is ``[num_blocks, block_size, kv_heads,
head_dim]`` — block-major so a block is contiguous in HBM (transfer-friendly,
like the reference KVBM's fully-contiguous layout, lib/llm/src/block_manager/
layout.rs) with heads minor to keep per-head slices dense for TP sharding.

Every op that touches the cache also accepts the int8 form (ops/quant.py
``QuantizedKV``: int8 payload + per-block-per-kv-head f32 scales). Writes
quantize on the way in; gathers dequantize on the way out — so this file is
the numerics reference the Pallas kernels and the CPU tier-1 tests pin
against, float and int8 alike.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import (
    QuantizedKV,
    dequantize_blocks,
    is_quantized,
    quantize_blocks,
    requantize_token,
)

NEG_INF = -1e30


def _softcap(scores: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 style attention-logit softcapping: cap * tanh(scores/cap),
    applied post-scale and pre-mask (matches the HF reference ordering).
    None = untouched."""
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [S,h,d] x k [T,kvh,d] -> scores [S,h,T] with GQA head grouping."""
    S, h, d = q.shape
    T, kvh, _ = k.shape
    g = h // kvh
    qg = q.reshape(S, kvh, g, d)
    scores = jnp.einsum("skgd,tkd->skgt", qg.astype(jnp.float32), k.astype(jnp.float32))
    return scores.reshape(S, h, T)


def _gqa_values(weights: jax.Array, v: jax.Array) -> jax.Array:
    """weights [S,h,T] x v [T,kvh,d] -> out [S,h,d]."""
    S, h, T = weights.shape
    _, kvh, d = v.shape
    g = h // kvh
    wg = weights.reshape(S, kvh, g, T)
    out = jnp.einsum("skgt,tkd->skgd", wg, v.astype(jnp.float32))
    return out.reshape(S, h, d)


def _sink_softmax(scores: jax.Array, sinks: jax.Array) -> jax.Array:
    """Softmax over the key axis with attention-sink logits in the
    DENOMINATOR only (gpt-oss: a virtual key whose probability mass is
    dropped, damping every real weight). scores [..., T]; ``sinks``
    broadcastable to scores' leading dims."""
    m = jnp.maximum(jnp.max(scores, axis=-1), sinks)
    p = jnp.exp(scores - m[..., None])
    denom = jnp.sum(p, axis=-1) + jnp.exp(sinks - m)
    return p / denom[..., None]


def causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Plain causal self-attention for a single contiguous sequence.

    q,k,v: [S, heads/kv_heads, head_dim] -> [S, heads, head_dim].
    ``window``: sliding-window attention — key j visible to query i iff
    i - window < j <= i. ``sinks``: per-head [h] attention-sink logits
    (gpt-oss) folded into the softmax denominator."""
    S = q.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = _softcap(_gqa_scores(q, k) * scale, softcap)
    qi, kj = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = kj <= qi
    if window is not None:
        causal &= kj > qi - window
    scores = jnp.where(causal[:, None, :], scores, NEG_INF)
    if sinks is None:
        weights = jax.nn.softmax(scores, axis=-1)
    else:
        weights = _sink_softmax(scores, sinks.astype(jnp.float32))
    return _gqa_values(weights, v).astype(q.dtype)


def extend_attention(
    q: jax.Array,            # [S_new, h, d] queries for the new suffix
    k_ctx: jax.Array,        # [T_max, kvh, d] gathered context incl. new keys
    v_ctx: jax.Array,        # [T_max, kvh, d]
    q_positions: jax.Array,  # [S_new] absolute positions of the queries
    total_len: jax.Array,    # scalar: valid length of the context
    window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Prefix-extend attention: new tokens attend causally over (cached prefix
    + themselves). Used for prefill with device-side prefix-cache reuse and
    for chunked prefill continuation. Context is padded to T_max; invalid
    positions masked. ``window``/``sinks``: see causal_attention (the
    context layout is positional, so the window mask is absolute-position
    based)."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    T = k_ctx.shape[0]
    scores = _softcap(_gqa_scores(q, k_ctx) * scale, softcap)  # [S,h,T]
    key_pos = jnp.arange(T)
    valid = key_pos[None, :] < jnp.minimum(q_positions[:, None] + 1, total_len)
    if window is not None:
        valid &= key_pos[None, :] > q_positions[:, None] - window
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    if sinks is None:
        weights = jax.nn.softmax(scores, axis=-1)
    else:
        weights = _sink_softmax(scores, sinks.astype(jnp.float32))
    return _gqa_values(weights, v_ctx).astype(q.dtype)


def gather_kv(
    k_cache: jax.Array,      # [num_blocks, block_size, kvh, d] (or QuantizedKV)
    v_cache: jax.Array,
    block_table: jax.Array,  # [max_blocks] int32 (padded with 0)
) -> Tuple[jax.Array, jax.Array]:
    """Gather one sequence's KV pages into contiguous [max_blocks*bs, kvh, d].

    Quantized caches dequantize during the gather (f32 out): the HBM read is
    still the int8 payload + tiny scale rows, which is where the bandwidth
    win lives; every consumer casts to f32 for the matmuls anyway."""
    bs = k_cache.shape[1]
    mb = block_table.shape[0]
    if is_quantized(k_cache):
        k = dequantize_blocks(
            k_cache.data[block_table], k_cache.scale[block_table]
        )
        v = dequantize_blocks(
            v_cache.data[block_table], v_cache.scale[block_table]
        )
    else:
        k = k_cache[block_table]  # [max_blocks, bs, kvh, d]
        v = v_cache[block_table]
    return (
        k.reshape(mb * bs, *k.shape[2:]),
        v.reshape(mb * bs, *v.shape[2:]),
    )


def paged_decode_attention(
    q: jax.Array,             # [B, h, d] one query token per sequence
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32
    seq_lens: jax.Array,      # [B] int32 context length incl. current token
    window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Paged decode attention, batched: each query attends over its own pages.

    Pure-JAX formulation: per-sequence page gather via vmap; masked softmax.
    ``window``/``sinks``: see causal_attention. The decode query sits at
    position length-1, so the window admits key indices >= length - window.
    Sliding-window layers gather ONLY the window's trailing blocks (a
    static ceil(window/bs)+1 slice of the block table), so a 128-token
    window over a 128k context reads ~window keys, not the whole cache.
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    bs = k_cache.shape[1]
    if window is not None:
        wb = min((window + bs - 1) // bs + 1, block_tables.shape[1])

    def one(qb, table, length):
        if window is None:
            k, v = gather_kv(k_cache, v_cache, table)  # [T, kvh, d]
            key_pos = jnp.arange(k.shape[0])
            valid = key_pos < length
        else:
            # trailing-window gather: last wb table entries that cover
            # [length - window, length)
            nblocks = jnp.maximum((length + bs - 1) // bs, 1)
            start = jnp.maximum(nblocks - wb, 0)
            idx = start + jnp.arange(wb)
            sub = table[jnp.clip(idx, 0, table.shape[0] - 1)]
            k, v = gather_kv(k_cache, v_cache, sub)    # [wb*bs, kvh, d]
            key_pos = start * bs + jnp.arange(wb * bs)
            valid = (key_pos < length) & (key_pos >= length - window)
        h, d = qb.shape
        kvh = k.shape[1]
        g = h // kvh
        qg = qb.reshape(kvh, g, d)
        scores = _softcap(jnp.einsum(
            "kgd,tkd->kgt", qg.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale, softcap)                             # [kvh, g, T]
        scores = jnp.where(valid[None, None, :], scores, NEG_INF)
        if sinks is None:
            weights = jax.nn.softmax(scores, axis=-1)
        else:
            weights = _sink_softmax(
                scores, sinks.astype(jnp.float32).reshape(kvh, g)
            )
        out = jnp.einsum("kgt,tkd->kgd", weights, v.astype(jnp.float32))
        return out.reshape(h, d)

    return jax.vmap(one)(q, block_tables, seq_lens).astype(q.dtype)


def write_prefill_kv(
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d]
    v_cache: jax.Array,
    k_new: jax.Array,         # [S_pad, kvh, d] (S_pad multiple of bs)
    v_new: jax.Array,
    block_ids: jax.Array,     # [S_pad // bs] destination blocks for the span
    page_view: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter a contiguous span of new KV into its pages (prefill path).

    The caller pads S to a block multiple and supplies one destination block
    per chunk; padding rows land in a scratch block (block 0 by convention is
    reserved as scratch so garbage writes are harmless).

    Quantized caches quantize-on-write: prefill writes whole blocks, so the
    per-block amax (and thus the scale) is computed in one shot — no rescale
    ever needed on this path. The amax covers EVERY row passed, so callers
    must zero bucket-padding rows first (the engine's prefill attend does)
    or pad activations inflate the real tokens' scale.

    ``page_view``: write a float pool as the Pallas kernels read it,
    ``[num_blocks, bs * kvh, d]`` (the same bytes in the same pages). The
    seam (ops/paged_attention.write_chunk) asks for it where a kernel takes
    the pool whole: the TPU's layout assignment gives a scatter of
    ``[bs, kvh, d]`` windows a pool tiled over ``(bs, d)`` when ``kvh`` is
    under a sublane tile (4 rows of 128 lanes a token), the kernel's operand
    is tiled over ``(kvh, d)``, and the pool is copied there and back around
    every layer's write (PERF.md section 6, PR 40)."""
    if page_view and not is_quantized(k_cache):
        def put(cache, new):
            # each array's own rows: a family's second array may hold fewer
            nb, bs, kvh, d = cache.shape
            pages = cache.reshape(nb, bs * kvh, d).at[block_ids].set(
                new.reshape(-1, bs * kvh, d)
            )
            return pages.reshape(cache.shape)

        return put(k_cache, k_new), put(v_cache, v_new)
    bs = k_cache.shape[1]
    S = k_new.shape[0]
    k_blocks = k_new.reshape(S // bs, bs, *k_new.shape[1:])
    v_blocks = v_new.reshape(S // bs, bs, *v_new.shape[1:])
    if is_quantized(k_cache):
        kq, ks = quantize_blocks(k_blocks)
        vq, vs = quantize_blocks(v_blocks)
        return (
            QuantizedKV(
                k_cache.data.at[block_ids].set(kq),
                k_cache.scale.at[block_ids].set(ks),
            ),
            QuantizedKV(
                v_cache.data.at[block_ids].set(vq),
                v_cache.scale.at[block_ids].set(vs),
            ),
        )
    return k_cache.at[block_ids].set(k_blocks), v_cache.at[block_ids].set(v_blocks)


def write_decode_kv(
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,         # [B, kvh, d]
    v_new: jax.Array,
    block_ids: jax.Array,     # [B] destination block of each seq's current pos
    offsets: jax.Array,       # [B] offset within the block
) -> Tuple[jax.Array, jax.Array]:
    """Scatter one token per sequence into its page slot (decode path).

    Quantized caches do a read-modify-write of the ONE destination block per
    row: the block scale grows to cover the new token and the existing ints
    rescale once (ops/quant.requantize_token — a bit-exact no-op whenever the
    scale is unchanged, the common case). A write at offset 0 is the FIRST
    row of a freshly-(re)allocated block, so the inherited scale is a stale
    leftover from the block's previous occupant and is reset — otherwise a
    recycled block that once held large activations would quantize a small
    new token to zero. Inactive rows all target scratch block 0;
    duplicate-index write order there is undefined and harmless."""
    if is_quantized(k_cache):
        B = k_new.shape[0]
        rows = jnp.arange(B)
        fresh = (offsets == 0)[:, None]  # [B, 1] broadcast over kvh

        def wr(cache, x_new):
            s_base = jnp.where(fresh, 0.0, cache.scale[block_ids])
            blk, s_new, q_new = requantize_token(
                cache.data[block_ids], s_base, x_new
            )
            blk = blk.at[rows, offsets].set(q_new)
            return QuantizedKV(
                cache.data.at[block_ids].set(blk),
                cache.scale.at[block_ids].set(s_new),
            )

        return wr(k_cache, k_new), wr(v_cache, v_new)
    return (
        k_cache.at[block_ids, offsets].set(k_new),
        v_cache.at[block_ids, offsets].set(v_new),
    )


def ragged_paged_attention(
    q: jax.Array,             # [Tq, h, d] densely packed ragged queries
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d] (or QuantizedKV)
    v_cache: jax.Array,
    block_tables: jax.Array,  # [R, max_blocks] int32
    q_starts: jax.Array,      # [R] int32 offset of row r's segment in q
    q_lens: jax.Array,        # [R] int32 segment length (0 = empty row)
    seq_lens: jax.Array,      # [R] int32 context length incl. the row's
                              #     q_lens new tokens
    window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
    windows: Optional[jax.Array] = None,
) -> jax.Array:
    """Unified ragged paged attention, pure-JAX reference twin of
    ``ops.pallas_unified.ragged_paged_attention``.

    One call serves an arbitrary mix of prefill chunks and decode tokens:
    each ROW r owns the query tokens ``q[q_starts[r] : q_starts[r]+q_lens[r]]``
    (its new tokens, sitting at the TAIL of its context — token i of the
    segment is at absolute position ``seq_lens[r] - q_lens[r] + i``) and
    attends causally over its own pages. A decode row is ``q_len == 1``; a
    prefill chunk is ``q_len == chunk_len``; a spec-decode verify pass is a
    row with ``q_len == k+1``. Segments must be disjoint (gaps are fine —
    padding rows between segments belong to no row); ``q_len <= seq_len``
    per row. Tokens outside every segment, and rows with ``q_len == 0`` or
    ``seq_len == 0`` (inactive slots), return ZEROS.

    ``window`` applies one sliding-window bound to every row; ``windows``
    ([R] int32, ``<= 0`` = full attention) sets it per row — the form the
    Pallas kernel takes. ``sinks``/``softcap``: see causal_attention.

    This is the numerics reference the Pallas unified kernel pins against in
    interpret mode; the engine's mixed prefill+decode step uses it directly
    when ``use_pallas`` is off. O(R * Tq * T) — every row scores the whole
    packed buffer and masks — so it is a reference, not a fast path."""
    Tq = q.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    idx = jnp.arange(Tq)
    if windows is None and window is not None:
        windows = jnp.full(block_tables.shape[0], window, jnp.int32)
    windowed = windows is not None

    def one(table, q_start, q_len, seq_len, w):
        k, v = gather_kv(k_cache, v_cache, table)   # [T, kvh, d]
        local = idx - q_start
        member = (local >= 0) & (local < q_len) & (seq_len > 0)
        q_pos = seq_len - q_len + local
        scores = _softcap(_gqa_scores(q, k) * scale, softcap)  # [Tq, h, T]
        key_pos = jnp.arange(k.shape[0])
        lim = jnp.minimum(q_pos + 1, seq_len)
        valid = key_pos[None, :] < lim[:, None]
        if windowed:
            valid &= jnp.where(
                w > 0, key_pos[None, :] > q_pos[:, None] - w, True
            )
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        if sinks is None:
            weights = jax.nn.softmax(scores, axis=-1)
        else:
            weights = _sink_softmax(scores, sinks.astype(jnp.float32))
        out = _gqa_values(weights, v)               # [Tq, h, d] f32
        return jnp.where(member[:, None, None], out, 0.0)

    w_arg = (
        windows if windowed
        else jnp.zeros(block_tables.shape[0], jnp.int32)
    )
    outs = jax.vmap(one)(block_tables, q_starts, q_lens, seq_lens, w_arg)
    # segments are disjoint, so summing the per-row masked outputs packs them
    return jnp.sum(outs, axis=0).astype(q.dtype)


def paged_extend_attention(
    q: jax.Array,             # [B, S_new, h, d] candidate-token queries
    k_cache: jax.Array,       # [num_blocks, bs, kvh, d]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32
    start_pos: jax.Array,     # [B] absolute position of each row's q[0]
    total_lens: jax.Array,    # [B] context length incl. the S_new candidates
    window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Batched paged prefix-extend: every row attends its S_new new tokens
    causally over its OWN pages (which must already contain the new tokens'
    KV). The verify pass of speculative decoding
    (docs/speculative_decoding.md) is this shape: the seam's verify rows
    (ops/paged_attention.py) are ``query_len = k+1`` rows of the ragged
    kernel on its Pallas side and this op on its pure-JAX side (the ragged
    TWIN would score the whole packed buffer per row — O(B^2) verify FLOPs).

    vmap of gather_kv + extend_attention: pure JAX, any head layout the
    single-sequence ops accept (GQA, MQA/MLA-latent), window/sinks
    supported. Windowed rows gather only the trailing blocks covering
    [start - window + 1, start + S_new) — the queries all sit at the tail,
    so like paged_decode_attention a 128-token window over a long context
    reads ~window + S_new keys, not the whole table."""
    S_new = q.shape[1]
    bs = k_cache.shape[1]
    if window is not None:
        wb = min(
            (window + S_new + bs - 1) // bs + 1, block_tables.shape[1]
        )

    def one(qb, table, start, tlen):
        positions = start + jnp.arange(S_new)
        if window is None:
            k_ctx, v_ctx = gather_kv(k_cache, v_cache, table)
            return extend_attention(
                qb, k_ctx, v_ctx, positions, tlen, sinks=sinks,
                softcap=softcap,
            )
        nblocks = jnp.maximum((tlen + bs - 1) // bs, 1)
        first = jnp.maximum(nblocks - wb, 0)
        sub = table[jnp.clip(first + jnp.arange(wb), 0, table.shape[0] - 1)]
        k_ctx, v_ctx = gather_kv(k_cache, v_cache, sub)   # [wb*bs, kvh, d]
        # extend_attention masks by ABSOLUTE key position; the gathered
        # window starts at first*bs, so shift the query positions and the
        # valid length into the gathered frame
        off = first * bs
        return extend_attention(
            qb, k_ctx, v_ctx, positions - off, tlen - off,
            window=window, sinks=sinks, softcap=softcap,
        )

    return jax.vmap(one)(q, block_tables, start_pos, total_lens)


# ---------------------------------------------------------------------------
# learned sparse attention over a paged latent (models/mla.py, DSA)
# ---------------------------------------------------------------------------
#
# Layout of a latent layer's two paged arrays, both ``[num_blocks, block_size,
# rows, 128]`` (``rows = max(kv_lora_rank / 128, 2)``, the engine's
# ``num_kv_heads``; 128 lanes a row, so a token of either array is whole
# Mosaic tiles and can be copied alone; a family that shapes its page groups
# by layer kind, models/registry.page_shapes, gives the V array the ONE tile
# a step reads, 2 rows, and each group's K array its own latent's rows):
#   K array: the normalised latent ``c``, ``kv_lora_rank / 128`` rows a token;
#   V array: row 0 ``[k_pe | 0]`` (the rotated shared key), row 1 the index
#            key ``[kI | 0]`` on layers with an indexer; further rows unused.
# Values are the latent itself (``W_uv`` is applied past the softmax), so the
# V array holds no values: it is the layer's second kind of per-token state,
# under the same block ids as the first.
#
# What a kernel copies of the V array is a token's FIRST ``(2, 128)`` tile
# alone, rows 0 and 1 (512 of the token's ``rows * 256`` bytes, a strided copy
# out of the view ``[tokens, rows / 2, 2, 128]``); a pair of rows shares a
# 32-bit word, row 0 its low half. ``sparse_latent_attention`` copies a
# selected token's tile and keeps the low halves (``k_pe``),
# ``paged_index_keys`` a page's or a run of pages' and keeps the high halves
# (the index key), ``paged_latent_attention`` a page's or a run's and keeps
# the low halves (since PR 55; the whole token until then). Rows 2 and up are
# written by nothing and read by nothing.
#
# A latent WITHOUT an indexer may keep the same two arrays (row 1 of the
# second unwritten) and hands the seam a ``LatentQuery``: nothing selects,
# every causal key is attended (``paged_latent_attention`` below).

LATENT_LANES = 128
SEL_NONE = -1     # padding of a query's list of selected positions


@dataclasses.dataclass
class DsaQuery:
    """What a latent layer asks of the attention seam besides q, k and v.

    ``index_q`` [..., n, d] / ``index_w`` [..., n] (the indexer's queries and
    its head weights, already scaled) are set on a layer that selects; the
    seam scores them against the paged index keys, takes the ``topk`` largest
    causal scores a query and leaves the positions in ``selected`` [Tq, K]
    (ascending, ``SEL_NONE`` pads after them: ``dsa_select``). A layer that
    shares a selection hands the one it inherited in ``selected`` and no
    ``index_q``. Where it selects, the seam
    also leaves in ``index_chunk_reads`` what its read of the index keys
    takes by the chunk: the whole chunks of pages under the rows' tables and
    those of them that are runs of consecutive pages (two scalars;
    ops/pallas_sparse.index_chunk_reads). A trace-time object: it lives for
    one forward pass of one program."""

    scale: float                       # softmax scale, 1/sqrt(qk_head_dim)
    topk: int
    index_q: Optional[jax.Array] = None
    index_w: Optional[jax.Array] = None
    selected: Optional[jax.Array] = None
    index_chunk_reads: Optional[Tuple[jax.Array, jax.Array]] = None
    # the prefix of the seam's scopes in a device trace (``<scope>_index``,
    # ``<scope>_select``): a family names its own
    scope: str = "dsa"


@dataclasses.dataclass
class LatentQuery:
    """What a latent layer WITHOUT an indexer, its cache held as rows, asks
    of the attention seam besides q, k and v: every causal key. The seam
    leaves in ``chunk_reads`` what the launch reads by the chunk: the whole
    chunks under its rows' contexts and those of them that are runs of
    consecutive pages (two scalars; ops/pallas_latent.chunk_reads). A
    trace-time object, as a ``DsaQuery`` is.

    ``window``: a query at position ``i`` sees the keys ``i - window < j <=
    i`` alone (a sliding layer's latent, models/dots3_note.py); the seam then
    answers with the same launch under the name ``windowed_latent_attention``,
    which starts a row's walk at the chunk of pages its window starts in.
    ``scope`` prefixes the seam's scope in a device trace
    (``<scope>_attend``)."""

    scale: float                       # softmax scale (YaRN's factor in it)
    chunk_reads: Optional[Tuple[jax.Array, jax.Array]] = None
    window: Optional[int] = None
    scope: str = "latent"


# dsa_index_scores: the most bytes of one slab's float32 sum ``[rows, T]``, the
# head scan's carry. Under it the v5e compiler keeps the carry on chip from the
# first head to the last (``S(1)`` in the compiled text) and a head costs its
# product; over it the carry lives in HBM and every head reads and writes all
# of it (2 048 queries x 37 376 keys: 306 MB each way a head, 39 GB a call,
# 59.1 ms on a v5e where slabs of 512 rows take 12.4 and of 256 13.4: PERF.md
# section 6, PR 59). Alone, 512 rows x 37 376 keys (76.5 MB) stay on chip; IN
# A STEP PROGRAM, beside what else the step keeps there, they do not (the agent
# cell read 215 tokens/s at 80 MiB, 223 with no slabs, 287 at 64 MiB: 256 rows,
# 38 MB). 64 MiB also leaves 512 queries x 25 600 keys (52.4 MB, GLM-5.2's
# mixed step) the one slab they were.
INDEX_SLAB_BYTES = 64 * 2 ** 20


def index_slab_rows(Q: int, T: int) -> int:
    """Queries a slab of a chunk's index scores: all ``Q`` while their float32
    sum ``[Q, T]`` is at most ``INDEX_SLAB_BYTES``, else the largest power of
    two not over ``Q`` whose sum is (8, a tile's rows, at the least)."""
    if Q * T * 4 <= INDEX_SLAB_BYTES:
        return Q
    fit = max(8, INDEX_SLAB_BYTES // (4 * T))
    return 1 << (min(fit, Q).bit_length() - 1)


def by_slabs(fn, rows: int, *arrays: jax.Array) -> jax.Array:
    """``fn`` of the arrays' leading ``Q`` rows, ``rows`` of them at a time,
    one slab after the other; a last short slab is filled with zeros and its
    filling cut from the result."""
    Q = arrays[0].shape[0]
    slabs = -(-Q // rows)
    fill = slabs * rows - Q
    if fill:
        arrays = tuple(jnp.pad(a, ((0, fill),) + ((0, 0),) * (a.ndim - 1))
                       for a in arrays)
    out = jax.lax.map(
        lambda slab: fn(*slab),
        tuple(a.reshape(slabs, rows, *a.shape[1:]) for a in arrays),
    )
    out = out.reshape(slabs * rows, *out.shape[2:])
    return out[:Q] if fill else out


def _index_scores_head_by_head(iq: jax.Array, iw: jax.Array, keys: jax.Array) -> jax.Array:
    def head(acc, j):
        s = jnp.einsum("qd,td->qt", iq[:, j], keys,
                       preferred_element_type=jnp.float32)
        return acc + jax.nn.relu(s) * iw[:, j, None], None

    zeros = jnp.zeros((iq.shape[0], keys.shape[0]), jnp.float32)
    acc, _ = jax.lax.scan(head, zeros, jnp.arange(iq.shape[1]))
    return acc


def dsa_index_scores(iq: jax.Array, iw: jax.Array, keys: jax.Array) -> jax.Array:
    """``I[q, t] = sum_j iw[q, j] relu(iq[q, j] . keys[t])``: iq [Q, n, d],
    iw [Q, n] f32, keys [T, d] -> [Q, T] f32. All heads at once while the
    per-head scores are small (the decode rows), else head by head, a SLAB of
    ``index_slab_rows`` queries at a time (a chunk against a long context):
    the float32 sum over the heads is then a slab's, small enough to stay on
    chip through the heads, and is written once. (All heads at once would fit
    at any size: the v5e compiler fuses the sum into the product with no
    temporary. It is slower, 51.9 M cycles by the compiler's own model at
    2 048 queries x 64 heads x 37 376 keys where eight slabs head by head are
    21.1 M, which is why the head scan stays.) Rows are independent: the
    slabs' scores are bit for bit one slab's."""
    Q, n, _ = iq.shape
    T = keys.shape[0]
    if Q * n * T * 4 <= 2 ** 28:
        s = jnp.einsum("qjd,td->qjt", iq, keys,
                       preferred_element_type=jnp.float32)
        # a float32 sum, not a product on the matrix unit (which would round
        # the scores to bf16 first and move the cut)
        return jnp.sum(jax.nn.relu(s) * iw[:, :, None], axis=1)
    rows = index_slab_rows(Q, T)
    if rows >= Q:
        return _index_scores_head_by_head(iq, iw, keys)
    return by_slabs(lambda a, b: _index_scores_head_by_head(a, b, keys),
                    rows, iq, iw)


# dsa_select: positions a block of the list's compaction (four 32-bit words
# of a query's mask), and bits of the cut a counting round finds (a divisor of
# 32; 2 = sixteen passes of three counts, the least time on a v5e: PERF.md
# section 6, PR 57)
SELECT_BLOCK = 128
SELECT_BITS = 2


def _order_keys(x: jax.Array) -> jax.Array:
    """float32 -> the uint32 that orders as the float does (``-0.0`` just
    under ``+0.0``): all bits of a negative flipped, the sign bit of the rest
    set."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """keys [T, Q] uint32 -> [Q], the ``k``-th largest of each column, built
    from its most significant bits down: a candidate stays if at least ``k``
    keys are ``>=`` it. A round is ONE pass over the keys (a reduce of every
    digit's count together), and nothing is sorted."""
    digits = jnp.arange(1, 1 << SELECT_BITS, dtype=jnp.uint32)
    rounds = 32 // SELECT_BITS

    def one(i, cut):
        shift = (rounds - 1 - i).astype(jnp.uint32) * SELECT_BITS
        cand = cut[None, :] | (digits[:, None] << shift)                # [D, Q]
        counts = jax.lax.reduce(
            tuple((keys >= c[None, :]).astype(jnp.int32) for c in cand),
            (jnp.int32(0),) * len(cand),
            lambda a, b: tuple(x + y for x, y in zip(a, b)), (0,),
        )
        digit = sum((c >= k).astype(jnp.uint32) for c in counts)       # counts fall
        return cut | (digit << shift)

    return jax.lax.fori_loop(0, rounds, one, jnp.zeros(keys.shape[1:], jnp.uint32))


def _pack_rows(m: jax.Array) -> jax.Array:
    """[T, Q] bool -> [T // 32, Q] uint32, row ``t`` at bit ``t % 32``."""
    T, Q = m.shape
    bit = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :, None]
    return jnp.sum(jnp.where(m.reshape(T // 32, 32, Q), bit, jnp.uint32(0)),
                   axis=1, dtype=jnp.uint32)


def _popcount(w: jax.Array) -> jax.Array:
    return jax.lax.population_count(w).astype(jnp.int32)


def _nth_set_bit(w: jax.Array, n: jax.Array) -> jax.Array:
    """The place of the ``n``-th set bit (from 0) of each uint32 word, for
    ``n`` under the word's count: halving, by the count of the low half."""
    pos = jnp.zeros_like(n)
    for half in (16, 8, 4, 2, 1):
        low = _popcount(w & jnp.uint32((1 << half) - 1))
        up = n >= low
        n, w, pos = (jnp.where(up, n - low, n), jnp.where(up, w >> half, w),
                     jnp.where(up, pos + half, pos))
    return pos


def _lowest_set_bits(w: jax.Array, n: jax.Array) -> jax.Array:
    """Each word's ``n`` lowest set bits (none for ``n <= 0``, all of them
    from the word's count on)."""
    last = _nth_set_bit(w, jnp.clip(n - 1, 0, 31)).astype(jnp.uint32)
    part = w & ((jnp.uint32(2) << last) - 1)
    return jnp.where(n >= _popcount(w), w, jnp.where(n <= 0, jnp.uint32(0), part))


def _set_positions(words: jax.Array, K: int) -> jax.Array:
    """words [B, 4, Q] uint32, a query's mask by blocks of ``SELECT_BLOCK``
    positions -> [K, Q] int32, the set positions ascending and ``SEL_NONE``
    after them. No scatter and no gather: slot ``j`` finds its block by
    comparing ``j`` with every block's running offset (one block holds it),
    takes that block's words in the same pass, and reads the place of its bit
    out of them."""
    B = words.shape[0]
    count = jnp.sum(_popcount(words), axis=1)                           # [B, Q]
    end = jnp.cumsum(count, axis=0)
    first = end - count
    id_bits = max(1, (B - 1).bit_length())
    if K << id_bits >= 2 ** 31:
        raise ValueError(f"a selection of {K} of {B} blocks of {SELECT_BLOCK} "
                         "positions does not fit the list's 32-bit tags")
    tag = (first << id_bits) | jnp.arange(B, dtype=jnp.int32)[:, None]
    j = jnp.arange(K, dtype=jnp.int32)[:, None, None]                   # [K, 1, 1]
    mine = (first[None] <= j) & (j < end[None])                         # [K, B, Q]
    held = jax.lax.reduce(
        (jnp.where(mine, tag[None], 0),)
        + tuple(jnp.where(mine, words[None, :, i], jnp.uint32(0)) for i in range(4)),
        (jnp.int32(0),) + (jnp.uint32(0),) * 4,
        lambda a, b: tuple(x | y for x, y in zip(a, b)), (1,),
    )
    tag, w = held[0], held[1:]                                          # [K, Q]
    block, r = tag & ((1 << id_bits) - 1), j[:, 0] - (tag >> id_bits)
    c0, c1, c2 = (_popcount(x) for x in w[:3])
    starts = (jnp.zeros_like(c0), c0, c0 + c1, c0 + c1 + c2)          # a word's first r
    which = sum((r >= s).astype(jnp.int32) for s in starts[1:])
    pick = [which == i for i in range(3)]
    place = _nth_set_bit(jnp.select(pick, w[:3], w[3]),
                         r - jnp.select(pick, starts[:3], starts[3]))
    pos = block * SELECT_BLOCK + which * 32 + place
    return jnp.where(j[:, 0] < end[-1][None, :], pos, SEL_NONE)


def dsa_select(scores: jax.Array, q_pos: jax.Array, q_valid: jax.Array,
               topk: int) -> jax.Array:
    """Exact top-k of the causal scores: scores [Q, T] float32, q_pos [Q] (a
    query sees keys ``t <= q_pos``) -> [Q, min(topk, T)] int32 positions, the
    selected ones first and ASCENDING, ``SEL_NONE`` after them. Every causal
    position is selected while there are at most ``topk`` of them; of equal
    scores at the cut the lower positions are kept (``lax.top_k``'s rule).

    No sort: the cut is found by counting (``_kth_largest``, 32 /
    ``SELECT_BITS`` passes over the scores as ordered keys), the keys above it
    and the first of the keys equal to it make a mask of one bit a position,
    and the list is read out of the mask's words (``_set_positions``). The
    passes run over the keys TRANSPOSED, queries on the lanes: a count is then
    a sum down the rows, and a word of the mask 32 rows of it. A score is a
    finite sum by construction; a NaN would sort by its bits, above ``+inf``
    with the sign bit clear (selected first), below every score and every
    masked key with it set (never selected)."""
    Q, T = scores.shape
    K = min(topk, T)
    Tp = -(-T // SELECT_BLOCK) * SELECT_BLOCK
    last = jnp.where(q_valid, q_pos, -1)                 # nothing seen: an empty row
    seen = jnp.arange(T, dtype=jnp.int32)[None, :] <= last[:, None]
    keys = _order_keys(jnp.where(seen, scores, -jnp.inf))
    if Tp != T:
        keys = jnp.pad(keys, ((0, 0), (0, Tp - T)))      # 0: under every key
    keys = keys.T                                        # [Tp, Q]
    cut = _kth_largest(keys, K)[None, :]
    above, equal = _pack_rows(keys > cut), _pack_rows(keys == cut)      # [Tp // 32, Q]
    # the bits of the positions a query sees: a masked key may equal the cut
    n_seen = last[None, :] + 1 - 32 * jnp.arange(Tp // 32, dtype=jnp.int32)[:, None]
    equal = equal & _lowest_set_bits(jnp.uint32(0xFFFFFFFF), n_seen)
    need = K - jnp.sum(_popcount(above), axis=0)
    n_equal = _popcount(equal)
    before = jnp.cumsum(n_equal, axis=0) - n_equal
    words = above | _lowest_set_bits(equal, need[None, :] - before)
    return _set_positions(words.reshape(Tp // SELECT_BLOCK, 4, Q), K).T


def paged_index_keys(v_cache: jax.Array, tables: jax.Array, dim: int) -> jax.Array:
    """The index keys (their first ``dim`` lanes) of each table's context:
    [R, max_blocks * bs, dim]. The pure-JAX twin of
    ``ops.pallas_sparse.paged_index_keys``: row 1 of a token shares its
    32-bit words with row 0 in the pool's tiling, so on a TPU this slice
    re-tiles the whole array first."""
    keys = v_cache[tables, :, 1, :dim]               # [R, mb, bs, dim]
    return keys.reshape(tables.shape[0], -1, dim)


def selected_token_rows(tables: jax.Array, rows: jax.Array, sel: jax.Array,
                        bs: int) -> jax.Array:
    """Selected absolute positions -> token rows of the flattened paged
    arrays (``block * bs + offset``), through each query's block table:
    tables [R, mb], rows [Tq] (the table of each query), sel [Tq, K]. Padding
    maps to the table's position 0, a real token row; the caller masks it."""
    pos = jnp.maximum(sel, 0)
    blocks = tables[rows[:, None], pos // bs]
    return blocks * bs + pos % bs


def sparse_latent_attention(
    q: jax.Array,            # [Tq, h, rank + 128]: [absorbed q | q_pe | 0]
    k_cache: jax.Array,      # [nb, bs, rows, 128] the latent
    v_cache: jax.Array,      # [nb, bs, rows, 128] row 0 = [k_pe | 0]
    tables: jax.Array,       # [R, mb]
    rows: jax.Array,         # [Tq] the table of each query
    sel: jax.Array,          # [Tq, K] selected positions, SEL_NONE pads
    scale: float,
) -> jax.Array:
    """MQA of every head of a query over ITS selected latent rows; values
    are the rows' latent. Returns [Tq, h, rank]; a query with nothing
    selected returns zeros. The pure-JAX twin of
    ``ops.pallas_sparse.sparse_latent_attention``."""
    nb, bs, r, lanes = k_cache.shape
    rank = q.shape[-1] - lanes
    tok = selected_token_rows(tables, rows, sel, bs)             # [Tq, K]
    c = k_cache.reshape(nb * bs, r * lanes)[tok][..., :rank]     # [Tq, K, rank]
    pe = v_cache.reshape(nb * bs, -1, lanes)[tok, 0]             # [Tq, K, 128]
    keys = jnp.concatenate([c, pe], axis=-1)
    s = jnp.einsum("qhd,qkd->qhk", q, keys,
                   preferred_element_type=jnp.float32) * scale
    ok = (sel >= 0)[:, None, :]
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom > 0, denom, 1.0)
    out = jnp.einsum("qhk,qkr->qhr", p, c.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_latent_attention(
    q: jax.Array,            # [Tq, h, rank + 128]: [absorbed q | q_pe | 0]
    k_cache: jax.Array,      # [nb, bs, rows, 128] the latent
    v_cache: jax.Array,      # [nb, bs, rows, 128] row 0 = [k_pe | 0]
    tables: jax.Array,       # [R, mb]
    q_starts: jax.Array,     # [R] offset of row r's queries in q
    q_lens: jax.Array,       # [R] queries of row r (0 = an empty row)
    seq_lens: jax.Array,     # [R] context length incl. the row's queries
    scale: float,
    window: Optional[int] = None,
) -> jax.Array:
    """MQA of every head of a query over EVERY causal latent row of its
    context (under ``window``: the last ``window`` of them, its own among
    them); values are the rows' latent. Ragged rows as in
    ``ragged_paged_attention``: row ``r`` owns ``q[q_starts[r] : q_starts[r]
    + q_lens[r]]`` at the tail of ``tables[r]``'s context. Returns [Tq, h,
    rank]; a query no row owns, or of an empty row, returns zeros. The
    pure-JAX twin of ``ops.pallas_latent.paged_latent_attention``; like the
    ragged twin every row scores the whole packed buffer: a reference."""
    nb, bs, r, lanes = k_cache.shape
    rank = q.shape[-1] - lanes
    idx = jnp.arange(q.shape[0])

    def one(table, q_start, q_len, seq_len):
        c = k_cache[table].reshape(-1, r * lanes)[:, :rank]      # [T, rank]
        pe = v_cache[table][:, :, 0].reshape(-1, lanes)          # [T, 128]
        keys = jnp.concatenate([c, pe], axis=-1)
        local = idx - q_start
        member = (local >= 0) & (local < q_len) & (seq_len > 0)
        q_pos = seq_len - q_len + local
        s = jnp.einsum("qhd,td->qht", q, keys,
                       preferred_element_type=jnp.float32) * scale
        key = jnp.arange(keys.shape[0])[None, :]
        seen = key < jnp.minimum(q_pos + 1, seq_len)[:, None]
        if window is not None:
            seen &= key > (q_pos - window)[:, None]
        s = jnp.where(seen[:, None, :], s, NEG_INF)
        out = jnp.einsum("qht,tr->qhr", jax.nn.softmax(s, axis=-1),
                         c.astype(jnp.float32))
        return jnp.where(member[:, None, None], out, 0.0)

    outs = jax.vmap(one)(tables, q_starts, q_lens, seq_lens)
    return jnp.sum(outs, axis=0).astype(q.dtype)


# ---------------------------------------------------------------------------
# EVA (models/evabyte.py): exact attention inside the query's window, one
# learned summary a chunk of every window before it.
#
# What a request keeps, both in the pool's arrays. Its PAGES are a ring of
# ``window / page`` entries (position ``p`` in entry ``(p mod window) //
# page``), written again by the next window. Its SUMMARIES are kept by
# window in summary blocks: block ``s`` is the pool's pages ``base +
# s * ppb .. + ppb`` (``ppb = window / chunk / page``, a window's summaries
# fill whole pages), summary ``i`` of the window a "token" at offset ``i``
# of the block. A row's table is its ring's entries, then one summary block
# a window (``[ring | blocks]``).
#
# To every attention launch such a row is ONE paged sequence (``eva_paged_
# view``): the pages of its closed windows' summaries, then its ring's, the
# query at the tail. Causal attention over that sequence IS the layer's one
# softmax over both sets, so the dense family's twins and kernels serve it
# with no change and nothing is merged.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EvaQuery:
    """What an EVA layer asks of the attention seam besides q, k and v: its
    two learned vectors a head and the window's geometry. A trace-time
    object, as a ``DsaQuery`` is."""

    mu: jax.Array                      # [h, d] the summary key's vector
    phi: jax.Array                     # [h, d] the summary value's vector
    window: int
    chunk: int

    @property
    def chunks_per_window(self) -> int:
        return self.window // self.chunk


def eva_summarise(k: jax.Array, v: jax.Array, eva: EvaQuery
                  ) -> Tuple[jax.Array, jax.Array]:
    """``k``, ``v`` [..., C, h, d] (a chunk's rotated keys and its values) ->
    the chunk's summary key and value [..., h, d], both softmaxes over the
    chunk's ``C`` positions, unscaled, in float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    mu, phi = eva.mu.astype(jnp.float32), eva.phi.astype(jnp.float32)
    a = jax.nn.softmax(jnp.sum(kf * mu, axis=-1), axis=-2)          # [..., C, h]
    b = jax.nn.softmax(
        jnp.sum(kf * phi, axis=-1) - 0.5 * jnp.sum(kf * kf, axis=-1), axis=-2
    )
    return (
        jnp.sum(a[..., None] * kf, axis=-3).astype(k.dtype),
        jnp.sum(b[..., None] * vf, axis=-3).astype(v.dtype),
    )


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  eva: EvaQuery) -> jax.Array:
    """One whole sequence from nothing: ``q``, ``k``, ``v`` [S, h, d], ``S``
    a whole number of chunks. The twin of what the seam serves from a ring
    and a store: query ``n`` over the keys ``m <= n`` of its window and the
    summaries of the chunks of the windows before it, one softmax."""
    S, h, d = q.shape
    W, C = eva.window, eva.chunk
    ks, vs = eva_summarise(
        k.reshape(S // C, C, h, d), v.reshape(S // C, C, h, d), eva
    )
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    n = jnp.arange(S)
    exact = (n[None, :] <= n[:, None]) & (n[None, :] // W == n[:, None] // W)
    seen = (jnp.arange(S // C)[None, :] * C) // W < n[:, None] // W
    scores = jnp.concatenate([
        jnp.where(exact[:, None, :], _gqa_scores(q, k) * scale, NEG_INF),
        jnp.where(seen[:, None, :], _gqa_scores(q, ks) * scale, NEG_INF),
    ], axis=-1)
    weights = jax.nn.softmax(scores, axis=-1)
    return (
        _gqa_values(weights[..., :S], v) + _gqa_values(weights[..., S:], vs)
    ).astype(q.dtype)


def eva_pages_per_block(eva: EvaQuery, page: int) -> int:
    """Pages of one summary block: a window's summaries fill whole pages
    (at the published sizes 128 summaries, 8 pages of 16)."""
    if eva.chunk != page or eva.chunks_per_window % page:
        raise ValueError(
            f"an EVA ring needs page == chunk ({page} / {eva.chunk}) and a "
            f"window's {eva.chunks_per_window} summaries to fill whole pages"
        )
    return eva.chunks_per_window // page


def eva_paged_view(tables: jax.Array, seq_lens: jax.Array, eva: EvaQuery,
                   page: int, base: int) -> Tuple[jax.Array, jax.Array]:
    """``tables`` [R, ring pages + windows] (a row's ring, then its summary
    blocks by window), ``seq_lens`` [R] (the context's length up to the
    row's last query; 0 = an empty row) -> the rows as ONE paged sequence
    each: tables [R, (windows - 1) ppb + ring pages] of the pages of the
    closed windows' summaries and then the ring's, and its lengths (the
    summaries visible, one key each, plus the positions of the open window
    up to the last query). Entries past a row's length are never read."""
    ppb = eva_pages_per_block(eva, page)
    rp = eva.window // page
    ring, blocks = tables[:, :rp], tables[:, rp:]
    n_win = blocks.shape[1]
    w = jnp.maximum(seq_lens - 1, 0) // eva.window       # the open window
    j = jnp.arange((n_win - 1) * ppb + rp)[None, :]
    from_summaries = base + jnp.take_along_axis(
        blocks, jnp.minimum(j // ppb, n_win - 1), axis=1
    ) * ppb + j % ppb
    from_ring = jnp.take_along_axis(
        ring, jnp.clip(j - (w * ppb)[:, None], 0, rp - 1), axis=1
    )
    view = jnp.where(j < (w * ppb)[:, None], from_summaries, from_ring)
    lens = jnp.where(
        seq_lens > 0, seq_lens - w * (eva.window - eva.chunks_per_window), 0
    )
    return view.astype(jnp.int32), lens.astype(jnp.int32)


def eva_summary_slots(tables: jax.Array, positions: jax.Array,
                      full: jax.Array, eva: EvaQuery, page: int, base: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """(page, offset) in the pool of the summary of the chunk that holds
    ``positions`` ([N], in the rows of ``tables`` [N, ...]); scratch page 0
    where ``full`` is false (the chunk's page is not whole yet)."""
    ppb = eva_pages_per_block(eva, page)
    rp = eva.window // page
    block = jnp.take_along_axis(
        tables[:, rp:], (positions // eva.window)[:, None], axis=1
    )[:, 0]
    i = (positions % eva.window) // eva.chunk            # summary of the window
    return (
        jnp.where(full, base + block * ppb + i // page, 0),
        jnp.where(full, i % page, 0),
    )


def eva_paged_decode_attention(q: jax.Array, k_cache: jax.Array,
                               v_cache: jax.Array, tables: jax.Array,
                               seq_lens: jax.Array, eva: EvaQuery,
                               base: int) -> jax.Array:
    """Decode rows over a ring and summary blocks (``tables`` [B, ring pages
    + windows], ``seq_lens`` the contexts' lengths with the fed token): the
    pure-JAX twin of ops/pallas_eva.eva_decode_attention."""
    view, lens = eva_paged_view(tables, seq_lens, eva, k_cache.shape[1], base)
    return paged_decode_attention(q, k_cache, v_cache, view, lens)


# ---------------------------------------------------------------------------
# Block-sparse attention over POOLED KEYS (InfLLM-v2, MiniCPM4 arXiv
# 2506.07900; models/minicpm_sala.py). A page layer keeps, beside its pages,
# ONE POOLED KEY a page a kv head: key ``j`` is the mean of the ``kernel``
# keys from ``j * stride`` on, with ``stride`` the page and ``kernel`` two
# pages, so it is a function of pages ``j`` and ``j + 1`` and FINAL when page
# ``j + 1`` fills. It is kept BY BLOCK ID, under the id of page ``j``, in the
# K pool's pages above the requests' (``base`` on: page ``base + id // page``,
# row ``id % page``; the same rows of the V pool hold nothing), so that it
# rides every step program inside the array it already takes, returns and
# donates. Past ``dense_len`` keys a query attends over the blocks of
# ``block`` keys it CHOOSES, a kv head: the forced ones (the first
# ``init_blocks`` and the ``window // block + 1`` that end at its own) and the
# ``topk`` best of the others by the pooled scores.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InfLlmQuery:
    """What a block-sparse layer asks of the attention seam besides q, k and
    v: the sizes of its selection. A trace-time object, as an ``EvaQuery``."""

    kernel: int                        # keys a pooled key averages
    stride: int                        # keys between two pooled keys: the page
    block: int                         # keys a block that is chosen whole
    topk: int                          # blocks chosen beside the forced ones
    init_blocks: int                   # forced: the context's first blocks
    window: int                        # forced: the blocks that end at the query's
    dense_len: int                     # contexts up to here attend every key
    # where the seam leaves what each decode launch was HANDED, for the
    # family's counters: the views' lengths [R, kvh], once a layer
    handed: Optional[list] = dataclasses.field(default=None, compare=False, repr=False)

    def __post_init__(self):
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                f"a pooled key is two pages ({self.kernel} / {self.stride}) "
                f"and a block whole pages ({self.block})"
            )

    @property
    def per_block(self) -> int:
        """Pooled keys (and pages) a block."""
        return self.block // self.stride

    @property
    def local_blocks(self) -> int:
        return self.window // self.block + 1

    @property
    def max_chosen(self) -> int:
        return self.topk + self.init_blocks + self.local_blocks


def infllm_pool_pages(num_blocks: int, page: int) -> int:
    """Pages the K pool holds above ``num_blocks`` for the pooled keys: one
    row a block id."""
    return -(-num_blocks // page)


def _infllm_slots(ids: jax.Array, page: int, base: int):
    """(page, row) in the K pool of the pooled key kept under block ``ids``
    (scratch block 0's slot is scratch too)."""
    return base + ids // page, ids % page


def _page_pair_mean(pages: jax.Array) -> jax.Array:
    """[..., 2, page, kvh, d] -> the pooled key [..., kvh, d] float32: each
    page summed, then the two (one order of summation wherever a key is
    pooled)."""
    sums = jnp.sum(pages.astype(jnp.float32), axis=-3)
    return (sums[..., 0, :, :] + sums[..., 1, :, :]) / (2 * pages.shape[-3])


def infllm_pool_chunk(k_cache: jax.Array, k_new: jax.Array, table: jax.Array,
                      chunk_start, total_len, spec: InfLlmQuery,
                      base: int, page_view: bool = False) -> jax.Array:
    """The pooled keys a chunk makes final (``k_new`` [S_pad, kvh, d] from
    the page-aligned ``chunk_start``, real up to ``total_len``): key ``j``
    for every page ``j + 1`` the chunk fills, the first of them from the
    page BEFORE the chunk, read back from the pool. Returns the K pool.
    ``page_view``: read and write the pool as the Pallas kernels read it and
    as the chunk's own pages were just written (``write_prefill_kv`` has the
    reason: a write in another view has the pool copied there and back)."""
    bs = k_cache.shape[1]
    if spec.stride != bs:
        raise ValueError(f"a pooled key's stride is the page: {spec.stride} != {bs}")
    n = k_new.shape[0] // bs
    first = chunk_start // bs
    nb, _, kvh, d = k_cache.shape
    rows = k_cache.reshape(nb, bs * kvh, d) if page_view else k_cache
    before = rows[table[jnp.maximum(first - 1, 0)]].reshape(bs, kvh, d)
    pages = jnp.concatenate([before[None], k_new.reshape(n, bs, kvh, d)])
    pooled = _page_pair_mean(jnp.stack([pages[:-1], pages[1:]], axis=1))
    j = first - 1 + jnp.arange(n)
    final = (j >= 0) & ((j + 2) * bs <= total_len)
    ids = jnp.where(final, table[jnp.clip(j, 0, table.shape[0] - 1)], 0)
    page, row = _infllm_slots(ids, bs, base)
    pooled = pooled.astype(k_cache.dtype)
    if not page_view:
        return k_cache.at[page, row].set(pooled)
    at = (row * kvh)[:, None] + jnp.arange(kvh)[None]
    return rows.at[page[:, None], at].set(pooled).reshape(k_cache.shape)


def infllm_pool_rows(k_cache: jax.Array, tables: jax.Array, seq_lens: jax.Array,
                     write_blocks: jax.Array, write_offsets: jax.Array,
                     spec: InfLlmQuery, base: int) -> jax.Array:
    """Decode rows, their token written at ``(write_blocks, write_offsets)``
    (scratch page 0: not a live row): a row whose token FILLED its page
    makes the pooled key of the page before it final, from the two pages
    read back whole. Returns the K pool."""
    bs = k_cache.shape[1]
    pos = jnp.maximum(seq_lens - 1, 0)
    final = (write_blocks > 0) & (write_offsets == bs - 1) & (pos >= spec.kernel - 1)
    j = jnp.maximum(pos // bs - 1, 0)
    two = jnp.take_along_axis(tables, jnp.stack([j, j + 1], axis=1), axis=1)
    pooled = _page_pair_mean(k_cache[two])                       # [R, kvh, d]
    ids = jnp.where(final, two[:, 0], 0)
    return k_cache.at[_infllm_slots(ids, bs, base)].set(pooled.astype(k_cache.dtype))


def infllm_pooled_keys(k_cache: jax.Array, tables: jax.Array, base: int) -> jax.Array:
    """``tables`` [R, mb] -> the pooled keys kept under their entries [R, mb,
    kvh, d] (an entry whose key is not final yet holds what was there). Read
    through the pool's page view, as every launch reads it."""
    nb, bs, kvh, d = k_cache.shape
    page, row = _infllm_slots(tables, bs, base)
    at = (row * kvh)[..., None] + jnp.arange(kvh)
    return k_cache.reshape(nb, bs * kvh, d)[page[..., None], at]


def infllm_select(q: jax.Array, pooled: jax.Array, n_keys: jax.Array,
                  spec: InfLlmQuery) -> Tuple[jax.Array, jax.Array]:
    """The blocks each query chooses, a kv head. ``q`` [R, h, d] at the end
    of contexts of ``n_keys`` [R] keys; ``pooled`` [R or 1, mb, kvh, d] (one
    set for all: the queries are one row's chunk). A head's softmax over the
    pooled keys that are final for the query, summed over the heads of a kv
    head, max-pooled to blocks (``per_block + 1`` wide from one key before
    the block's first); forced blocks first, then the ``topk`` best of the
    blocks before the window. Returns (blocks [R, kvh, K] ascending, the
    places past ``count`` holding ``nb``; count [R, kvh])."""
    R, h, d = q.shape
    mb, kvh = pooled.shape[1:3]
    ppb = spec.per_block
    nb = -(-mb // ppb)
    qg = q.reshape(R, kvh, h // kvh, d).astype(jnp.float32)
    pk = pooled.astype(jnp.float32)
    s = (jnp.einsum("rkgd,jkd->rkgj", qg, pk[0]) if pk.shape[0] == 1
         else jnp.einsum("rkgd,rjkd->rkgj", qg, pk)) * d ** -0.5
    n_pool = jnp.maximum((n_keys - spec.kernel) // spec.stride + 1, 0)
    final = (jnp.arange(mb)[None] < n_pool[:, None])[:, None, None]
    p = jnp.where(final, jax.nn.softmax(jnp.where(final, s, NEG_INF), axis=-1), 0.0)
    group = jnp.sum(p, axis=2)                                   # [R, kvh, mb]
    # a block's score: the max over its pooled keys and the one before them
    padded = jnp.pad(group, ((0, 0), (0, 0), (1, nb * ppb - mb)), constant_values=-1.0)
    score = padded[..., 0::ppb][..., :nb]
    for i in range(1, ppb + 1):
        score = jnp.maximum(score, padded[..., i::ppb][..., :nb])
    b = jnp.arange(nb)[None]
    last = (jnp.maximum(n_keys - 1, 0) // spec.block)[:, None]
    forced = (b <= last) & ((b < spec.init_blocks) | (b > last - spec.local_blocks))
    cand = (b <= last - spec.local_blocks) & (b >= spec.init_blocks)
    key = jnp.where(forced[:, None], 1e9, jnp.where(cand[:, None], score, -1e9))
    K = min(spec.max_chosen, nb)
    _, idx = jax.lax.top_k(key, K)
    count = (jnp.sum(forced, axis=-1) + jnp.minimum(jnp.sum(cand, axis=-1), spec.topk))
    count = jnp.minimum(count, K)[:, None]
    blocks = jnp.sort(jnp.where(jnp.arange(K)[None, None] < count[..., None], idx, nb), axis=-1)
    return blocks.astype(jnp.int32), jnp.broadcast_to(count, (R, kvh)).astype(jnp.int32)


def infllm_view_width(spec: InfLlmQuery, page: int, max_blocks: int) -> int:
    """Entries of a (row, kv head)'s table: the chosen blocks' pages, or
    every page of a context that attends densely, whichever is more."""
    return min(max_blocks, max(spec.max_chosen * spec.per_block, -(-spec.dense_len // page)))


def infllm_paged_view(tables: jax.Array, n_keys: jax.Array, blocks: jax.Array,
                      count: jax.Array, spec: InfLlmQuery, page: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Decode rows as ONE paged sequence a (row, kv head): ``tables`` [R, mb],
    ``n_keys`` [R] (0 = an empty row), ``blocks`` / ``count`` as
    ``infllm_select`` gives them -> tables [R * kvh, W] and lengths [R *
    kvh]. Past ``dense_len`` a view holds the pages of the blocks the kv head
    chose, ascending, so the query's own block comes last and alone may be
    partial; up to ``dense_len`` it holds the row's own pages. Entries past a
    view's length are never read."""
    R, mb = tables.shape
    kvh = blocks.shape[1]
    ppb = spec.per_block
    W = infllm_view_width(spec, page, mb)
    e = jnp.arange(W)
    of_block = jnp.take_along_axis(
        blocks, jnp.minimum(e // ppb, blocks.shape[-1] - 1)[None, None], axis=-1
    ) * ppb + e % ppb                                            # [R, kvh, W]
    chosen = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None], (R, kvh, mb)), jnp.minimum(of_block, mb - 1), axis=-1
    )
    sparse = (n_keys > spec.dense_len)[:, None]
    view = jnp.where(sparse[..., None], chosen, tables[:, None, :W])
    last_fill = jnp.maximum(n_keys - 1, 0) % spec.block + 1
    lens = jnp.where(sparse, (count - 1) * spec.block + last_fill[:, None], n_keys[:, None])
    return (view.reshape(R * kvh, W).astype(jnp.int32),
            lens.reshape(R * kvh).astype(jnp.int32))


def infllm_decode_rows(q: jax.Array, k_cache: jax.Array, tables: jax.Array,
                       seq_lens: jax.Array, spec: InfLlmQuery, base: int):
    """What a decode launch over the chosen pages takes: the queries once a
    kv head [R * kvh, h, d], the views and their lengths."""
    kvh = k_cache.shape[2]
    with jax.named_scope("infllm_select"):
        blocks, count = infllm_select(
            q, infllm_pooled_keys(k_cache, tables, base), seq_lens, spec
        )
        view, lens = infllm_paged_view(tables, seq_lens, blocks, count, spec, k_cache.shape[1])
    if spec.handed is not None:
        spec.handed.append(lens.reshape(-1, kvh))
    return jnp.repeat(q, kvh, axis=0), view, lens


def infllm_own_heads(out: jax.Array, kvh: int) -> jax.Array:
    """[R * kvh, h, d], view (r, i)'s result for every head -> [R, h, d]:
    each head from the view of its own kv head."""
    RK, h, d = out.shape
    o = out.reshape(RK // kvh, kvh, kvh, h // kvh, d)
    i = jnp.arange(kvh)
    return o[:, i, i].reshape(RK // kvh, h, d)


def infllm_paged_decode_attention(q: jax.Array, k_cache: jax.Array,
                                  v_cache: jax.Array, tables: jax.Array,
                                  seq_lens: jax.Array, spec: InfLlmQuery,
                                  base: int) -> jax.Array:
    """Decode rows of a block-sparse layer: the pure-JAX twin of the launch
    ``infllm_decode_attention`` (ops/paged_attention.py)."""
    qv, view, lens = infllm_decode_rows(q, k_cache, tables, seq_lens, spec, base)
    out = paged_decode_attention(qv, k_cache, v_cache, view, lens)
    return infllm_own_heads(out, k_cache.shape[2])


# queries a pass of the masked chunk attention: [h, Q, keys] float32 scores
INFLLM_QUERY_BLOCK = 128


def _infllm_masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             pooled: jax.Array, n_keys: jax.Array,
                             spec: InfLlmQuery) -> jax.Array:
    """Queries ``q`` [Q, h, d], each at the end of ``n_keys`` [Q] of the keys
    ``k``, ``v`` [T, kvh, d] of ONE sequence whose pooled keys are ``pooled``
    [1, mb, kvh, d]: every query's own choice of blocks as a mask over all
    ``T`` keys (every causal key where ``n_keys <= dense_len``)."""
    Q, h, d = q.shape
    T, kvh = k.shape[:2]
    nb = -(-pooled.shape[1] // spec.per_block)
    with jax.named_scope("infllm_select"):
        blocks, _ = infllm_select(q, pooled, n_keys, spec)
        chosen = jnp.any(blocks[..., None] == jnp.arange(nb), axis=-2)   # [Q, kvh, nb]
    mask = jnp.repeat(chosen, spec.block, axis=-1)[..., :T]
    mask = mask | (n_keys <= spec.dense_len)[:, None, None]
    mask = mask & (jnp.arange(T)[None] < n_keys[:, None])[:, None]
    scores = _gqa_scores(q, k).reshape(Q, kvh, h // kvh, T) * d ** -0.5
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).reshape(Q, h, T)
    return _gqa_values(weights, v).astype(q.dtype)


def infllm_chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                           table: jax.Array, positions: jax.Array, total_len,
                           spec: InfLlmQuery, base: int) -> jax.Array:
    """One chunk at its context's tail with queries past ``dense_len``:
    every query's own choice of blocks as a MASK over the row's gathered
    pages (the mathematics whole, the cost dense: the block-sparse prefill
    kernel is queued, ROADMAP R23 (a)), ``INFLLM_QUERY_BLOCK`` queries a
    pass. ``q`` [S_pad, h, d] at ``positions``; the chunk's keys and pooled
    keys are already in the pool."""
    S, h, d = q.shape
    nb_pool, bs, kvh, _ = k_cache.shape
    T = table.shape[0] * bs
    # gathered through the page view (a 4-d gather has the pool re-tiled)
    k_ctx, v_ctx = (c.reshape(nb_pool, bs * kvh, d)[table].reshape(T, kvh, d)
                    for c in (k_cache, v_cache))
    with jax.named_scope("infllm_select"):
        pooled = infllm_pooled_keys(k_cache, table[None], base)
    QB = math.gcd(S, INFLLM_QUERY_BLOCK)

    def one(args):
        qb, pos = args
        return _infllm_masked_attention(
            qb, k_ctx, v_ctx, pooled, jnp.minimum(pos + 1, total_len), spec)

    out = jax.lax.map(one, (q.reshape(S // QB, QB, h, d), positions.reshape(S // QB, QB)))
    return out.reshape(S, h, d)


def infllm_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     spec: InfLlmQuery) -> jax.Array:
    """A whole sequence from nothing (``q`` [S, h, d]; no pages, no pool):
    the stateless twin, every pooled key computed from the keys themselves."""
    S, _, d = q.shape
    kvh = k.shape[1]
    mb = -(-S // spec.stride)
    kp = jnp.pad(k.astype(jnp.float32), ((0, (mb + 1) * spec.stride - S), (0, 0), (0, 0)))
    pages = kp.reshape(mb + 1, spec.stride, kvh, d)
    pooled = _page_pair_mean(jnp.stack([pages[:-1], pages[1:]], axis=1))[None]
    return _infllm_masked_attention(q, k, v, pooled, jnp.arange(S) + 1, spec)
