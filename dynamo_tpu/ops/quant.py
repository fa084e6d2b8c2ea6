"""Int8 paged-KV quantization: the storage format and its numerics.

Decode is taken to be HBM-bandwidth-bound, with paged-KV reads dominating
the per-step bytes at batch (from earlier chip runs, since deleted; not
measured on today's code).
Storing the paged cache as int8 with per-block-per-kv-head float32 scales
halves the KV bytes on every path that touches them — the HBM page reads in
both attention kernels, the disagg transfer wire, and the KVBM host/disk
tiers — and doubles effective KV capacity. This is the standard bandwidth
lever behind Ragged Paged Attention's TPU kernel work (PAPERS: arxiv
2604.15464) and FlowKV's low-latency KV transfer (arxiv 2504.03775).

Format, shared by every layer of the stack (device cache, Pallas kernels,
transfer wire, KVBM block codec):

    payload : int8  [..., block_size, kv_heads, head_dim]
    scale   : f32   [..., kv_heads]      (amax over the block's positions
                                          and head_dim, divided by 127)

Quantization is symmetric round-to-nearest:  q = rint(x / scale) in
[-127, 127];  dequant = q * scale.  Two properties tests rely on:

  - round-trip error per element is bounded by scale/2 = amax/254;
  - for a SCALE-SATURATED block (fresh quantize_blocks output: max|q| ==
    127 by construction) dequantize -> requantize reproduces (payload,
    scale) BIT-EXACTLY — the recomputed amax equals 127*scale and the ints
    re-round to themselves — which is what makes float<->int8 engine
    handoffs over the transfer plane lossless past the first quantization.
    A block whose scale later GREW via requantize_token (a decode write
    raised the amax) has max|q| < 127, so a float round trip of it is
    quantization-tolerance-equivalent rather than bit-exact; int8<->int8
    moves (transfer, KVBM) ship the pair untouched and stay bit-exact
    always.

``QuantizedKV`` is the device-side pair, registered as a JAX pytree so the
engine's cache lists, jit donation, shard_map specs, and the multi-layer
scan carries treat it exactly like the raw array it replaces.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# dtype of the per-block-per-kv-head scale rows everywhere (device, wire,
# KVBM codec). int8 payload + f32 scales is the whole format.
SCALE_DTYPE = np.dtype(np.float32)
KV_DTYPES = ("model", "int8")


def resolve_kv_dtype(value: str) -> str:
    """Resolve a config ``kv_dtype`` to one of KV_DTYPES. ``auto`` defers to
    the DTPU_KV_DTYPE env (default: model dtype — behavior unchanged)."""
    v = (value or "auto").lower()
    if v == "auto":
        v = os.environ.get("DTPU_KV_DTYPE", "model").lower() or "model"
    if v in ("none", "float", "fp", "cache"):
        v = "model"
    if v not in KV_DTYPES:
        raise ValueError(
            f"unknown kv_dtype {value!r} (DTPU_KV_DTYPE?): expected one of "
            f"{KV_DTYPES} or 'auto'"
        )
    return v


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedKV:
    """One paged KV cache array quantized to int8 + per-block scales.

    data  : int8 [num_blocks, block_size, kv_heads, head_dim]
    scale : f32  [num_blocks, kv_heads]

    ``.shape``/``.dtype`` mirror the payload so shape-probing call sites
    (``k_cache.shape[1]`` for block_size etc.) work unchanged.
    """

    data: Any
    scale: Any

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype


def is_quantized(cache: Any) -> bool:
    return isinstance(cache, QuantizedKV)


# ---------------------------------------------------------------- jnp kernels
def quantize_blocks(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., bs, kvh, d] float -> (int8 payload, f32 scale [..., kvh]).

    amax reduces over the block's positions AND head_dim (one scale per
    kv head per block); an all-zero block gets scale 0 and payload 0, and
    dequantizes to exact zeros."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-3, -1))              # [..., kvh]
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(
        jnp.rint(xf * inv[..., None, :, None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_blocks(q: jax.Array, scale: jax.Array) -> jax.Array:
    """(int8 [..., bs, kvh, d], f32 [..., kvh]) -> f32 [..., bs, kvh, d]."""
    return q.astype(jnp.float32) * scale[..., None, :, None]


def requantize_token(
    blk_q: jax.Array,      # int8 [..., bs, kvh, d] current block contents
    blk_scale: jax.Array,  # f32  [..., kvh] current block scale
    x_new: jax.Array,      # [..., kvh, d] the one new row (float)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode-write numerics: grow the block scale to cover the new row and
    rescale the existing ints once (ratio <= 1; when the scale is unchanged
    — the common case — ratio == 1 and the rescale is a bit-exact no-op).
    Returns (rescaled block ints, new scale, the new row quantized)."""
    a_new = jnp.max(jnp.abs(x_new.astype(jnp.float32)), axis=-1)   # [..., kvh]
    s_new = jnp.maximum(blk_scale, a_new / 127.0)
    inv = jnp.where(s_new > 0, 1.0 / s_new, 0.0)
    ratio = blk_scale * inv                                        # <= 1
    blk = jnp.rint(
        blk_q.astype(jnp.float32) * ratio[..., None, :, None]
    ).astype(jnp.int8)
    q_new = jnp.clip(
        jnp.rint(x_new.astype(jnp.float32) * inv[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return blk, s_new.astype(jnp.float32), q_new


# ---------------------------------------------------------------- np mirrors
def quantize_blocks_np(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side mirror of quantize_blocks (same formula, same rounding):
    used by the transfer client when importing float pages into an int8
    engine. Dequantize->requantize is bit-exact (see module docstring)."""
    xf = np.asarray(x, np.float32)
    amax = np.max(np.abs(xf), axis=(-3, -1))
    scale = (amax / 127.0).astype(np.float32)
    inv = np.where(scale > 0, 1.0 / scale, 0.0).astype(np.float32)
    q = np.clip(
        np.rint(xf * inv[..., None, :, None]), -127.0, 127.0
    ).astype(np.int8)
    return q, scale


def dequantize_blocks_np(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * np.asarray(scale, np.float32)[..., None, :, None]
