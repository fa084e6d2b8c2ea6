"""Block memory layouts: how one KV block's bytes are organized in a tier.

Analog of the reference's layout abstraction
(lib/llm/src/block_manager/layout.rs, FullyContiguous vs LayerSeparate):
the LOGICAL block is always [num_layers, 2, block_size, kv_heads, head_dim]
(K and V per layer), but tiers and transfer agents care about the physical
arrangement:

- **FullyContiguous** — one C-order buffer per block. What the wire formats
  and the disk tier want: a block is a single read/write.
- **LayerSeparate** — one buffer per layer (outer dim peeled off). What the
  DEVICE side produces and consumes: engine gathers/scatters are per-layer
  (k_caches/v_caches are per-layer arrays), so layer-separate storage avoids
  the [L, ...] -> [n, L, ...] transpose copy on every offload.

Both layouts expose the same views so tiers can store either way and
transfer code can convert only when crossing a boundary.

``BlockShape.dtype`` is the STORAGE dtype and has no default: callers must
derive it from the model (``block_shape_for``) — the old np.float32 default
silently made bf16 models pay 2x host-RAM and wire bytes per block. With
``kv_dtype="int8"`` the storage format is int8 payload + per-layer-per-K/V
per-kv-head f32 scales, and ``QuantizedBlockCodec`` packs the pair into ONE
flat uint8 buffer so every tier (host dict, disk file, remote store, native
arena) keeps treating a block as a single opaque byte run.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

from ..ops.quant import SCALE_DTYPE


@dataclasses.dataclass(frozen=True)
class BlockShape:
    num_layers: int
    block_size: int
    num_kv_heads: int
    head_dim: int
    dtype: np.dtype

    @property
    def logical_shape(self) -> Tuple[int, int, int, int, int]:
        return (self.num_layers, 2, self.block_size, self.num_kv_heads,
                self.head_dim)

    @property
    def layer_shape(self) -> Tuple[int, int, int, int]:
        return (2, self.block_size, self.num_kv_heads, self.head_dim)

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.logical_shape:
            n *= d
        return n * self.dtype.itemsize

    @property
    def layer_nbytes(self) -> int:
        return self.nbytes // self.num_layers


class FullyContiguous:
    """One buffer per block, logical C-order."""

    def __init__(self, shape: BlockShape):
        self.shape = shape

    def pack(self, per_layer: Sequence[np.ndarray]) -> np.ndarray:
        """[2, bs, kvh, d] x L -> one [L, 2, bs, kvh, d] buffer."""
        assert len(per_layer) == self.shape.num_layers
        return np.stack([np.asarray(p) for p in per_layer]).astype(
            self.shape.dtype, copy=False
        )

    def unpack(self, block: np.ndarray) -> List[np.ndarray]:
        block = block.reshape(self.shape.logical_shape)
        return [block[i] for i in range(self.shape.num_layers)]

    def layer_view(self, block: np.ndarray, layer: int) -> np.ndarray:
        return block.reshape(self.shape.logical_shape)[layer]

    def to_bytes(self, block: np.ndarray) -> bytes:
        return np.ascontiguousarray(block).tobytes()

    def from_bytes(self, raw: bytes) -> np.ndarray:
        return np.frombuffer(raw, self.shape.dtype).reshape(
            self.shape.logical_shape
        )


class LayerSeparate:
    """One buffer per layer: matches the engine's per-layer cache arrays, so
    device-side gathers land here without an extra stack/transpose."""

    def __init__(self, shape: BlockShape):
        self.shape = shape

    def pack(self, per_layer: Sequence[np.ndarray]) -> List[np.ndarray]:
        assert len(per_layer) == self.shape.num_layers
        return [
            np.ascontiguousarray(np.asarray(p), dtype=self.shape.dtype)
            for p in per_layer
        ]

    def unpack(self, block: List[np.ndarray]) -> List[np.ndarray]:
        return list(block)

    def layer_view(self, block: List[np.ndarray], layer: int) -> np.ndarray:
        return block[layer]

    def to_bytes(self, block: List[np.ndarray]) -> bytes:
        return b"".join(np.ascontiguousarray(p).tobytes() for p in block)

    def from_bytes(self, raw: bytes) -> List[np.ndarray]:
        n = self.shape.layer_nbytes
        return [
            np.frombuffer(raw[i * n:(i + 1) * n], self.shape.dtype).reshape(
                self.shape.layer_shape
            )
            for i in range(self.shape.num_layers)
        ]


def convert(block, src, dst):
    """Re-layout one block (copy only when crossing representations)."""
    if type(src) is type(dst):
        return block
    return dst.pack(src.unpack(block)) if isinstance(dst, LayerSeparate) else (
        np.stack(src.unpack(block))
    )


def make_layout(kind: str, shape: BlockShape):
    if kind in ("contiguous", "fully_contiguous", "fc"):
        return FullyContiguous(shape)
    if kind in ("layer_separate", "ls"):
        return LayerSeparate(shape)
    raise ValueError(f"unknown layout {kind!r}")


def dtype_from_name(name: str) -> np.dtype:
    """np.dtype('bfloat16') is only resolvable through ml_dtypes — the one
    name->dtype spot for block storage (disk tier headers, wire fields)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def block_shape_for(mcfg, block_size: int, kv_dtype: str = "model") -> BlockShape:
    """THE constructor for KV block shapes: storage dtype comes from the
    model config (bf16 models store bf16 blocks), or int8 for the quantized
    cache. Allocating a KV buffer with a raw np.float32 elsewhere is a lint
    finding (tools/lint.py KV-DTYPE). ``num_layers`` here is the model's
    LAYERS, one page each: a family whose block holds another number of
    SLOTS of pages (``models/registry.page_slots``: fewer where only some
    layers keep pages, more where a (pass, layer) keeps its own) is refused
    the tiers and the transfer plane where the gather would miss slots
    (``registry._WHY``), and ``engine.kv_bytes_per_block`` scales these bytes
    by ``page_slots / num_layers``."""
    dtype = np.dtype(np.int8) if kv_dtype == "int8" else np.dtype(mcfg.dtype)
    return BlockShape(
        num_layers=mcfg.num_layers,
        block_size=block_size,
        num_kv_heads=mcfg.num_kv_heads,
        head_dim=mcfg.head_dim,
        dtype=dtype,
    )


class QuantizedBlockCodec:
    """int8 block <-> one flat uint8 buffer (payload then scales).

    Logical quantized block:
      payload [L, 2, bs, kvh, d] int8
      scales  [L, 2, kvh]        f32  (per layer, per K/V, per kv head)

    encode/decode are pure byte moves — bit-exact round-trips by
    construction, which is what lets transfer/KVBM ship quantized blocks
    without ever touching the floats. ``shape.dtype`` must be int8."""

    def __init__(self, shape: BlockShape):
        assert shape.dtype == np.dtype(np.int8), shape
        self.shape = shape
        self.payload_shape = shape.logical_shape
        self.scales_shape = (shape.num_layers, 2, shape.num_kv_heads)
        self.payload_nbytes = int(np.prod(self.payload_shape))
        self.scales_nbytes = (
            int(np.prod(self.scales_shape)) * SCALE_DTYPE.itemsize
        )
        self.nbytes = self.payload_nbytes + self.scales_nbytes

    def encode(self, payload: np.ndarray, scales: np.ndarray) -> np.ndarray:
        """(payload [L,2,bs,kvh,d] int8, scales [L,2,kvh] f32) -> uint8 [nbytes]."""
        buf = np.empty(self.nbytes, np.uint8)
        buf[: self.payload_nbytes] = np.ascontiguousarray(
            payload.reshape(self.payload_shape).view(np.int8)
        ).view(np.uint8).reshape(-1)
        buf[self.payload_nbytes:] = np.ascontiguousarray(
            np.asarray(scales, SCALE_DTYPE).reshape(self.scales_shape)
        ).view(np.uint8).reshape(-1)
        return buf

    def decode(self, buf: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """uint8 [nbytes] -> (payload, scales). Zero-copy views."""
        flat = np.asarray(buf, np.uint8).reshape(-1)
        payload = flat[: self.payload_nbytes].view(np.int8).reshape(
            self.payload_shape
        )
        scales = flat[self.payload_nbytes:].view(SCALE_DTYPE).reshape(
            self.scales_shape
        )
        return payload, scales

    def decode_many(self, bufs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """uint8 [n, nbytes] -> (payload [n, L, 2, ...], scales [n, L, 2, kvh])."""
        n = bufs.shape[0]
        flat = np.ascontiguousarray(bufs, dtype=np.uint8).reshape(n, -1)
        payload = flat[:, : self.payload_nbytes].view(np.int8).reshape(
            (n,) + self.payload_shape
        )
        scales = np.ascontiguousarray(
            flat[:, self.payload_nbytes:]
        ).view(SCALE_DTYPE).reshape((n,) + self.scales_shape)
        return payload, scales


def kv_bytes_per_token(mcfg, block_size: int, kv_dtype: str = "model") -> float:
    """KV bytes one token occupies in the paged cache — the SAME number for
    HBM, the transfer wire, and a KVBM tier block, since all three store the
    identical format (block_shape_for / QuantizedBlockCodec). int8 amortizes
    the per-block scale rows over block_size positions; at d=64, bs=16 that
    lands ~0.51x of bf16 (the bench emits this so the win is measurable)."""
    shape = block_shape_for(mcfg, block_size, kv_dtype)
    if kv_dtype == "int8":
        return QuantizedBlockCodec(shape).nbytes / block_size
    return shape.nbytes / block_size
