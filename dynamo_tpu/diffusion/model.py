"""TPU-native image diffusion: a DiT-style denoiser + jitted DDIM sampler.

Backs the frontend's /v1/images/generations the way the reference backs it
with a real diffusion engine behind its SGLang worker
(components/src/dynamo/sglang/main.py:309,458 serves diffusion /
image-diffusion model types). This is the TPU-first equivalent, not a port:

- **DiT denoiser** (patchify -> transformer with AdaLN-zero timestep/prompt
  conditioning -> unpatchify), all bf16 matmuls with static shapes so XLA
  tiles every layer onto the MXU.
- **DDIM sampler under lax.fori_loop**: the entire multi-step denoise is ONE
  compiled XLA program — no per-step host round-trips (a dispatch and a
  readback per step otherwise).
- Prompt conditioning hashes tokens into an embedding table (weights are
  random unless a checkpoint is loaded — serving capability and the compute
  path are what's exercised; checkpoints drop in via the same param pytree).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    image_size: int = 64
    patch_size: int = 8
    hidden: int = 256
    layers: int = 6
    heads: int = 4
    mlp_ratio: int = 4
    cond_vocab: int = 8192     # hashed prompt-token conditioning ids
    cond_len: int = 16         # conditioning tokens per prompt
    steps: int = 30            # DDIM steps
    dtype: Any = jnp.bfloat16

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * 3


def init_params(cfg: DiffusionConfig, seed: int = 0) -> Dict[str, Any]:
    rng = np.random.default_rng(seed)
    h = cfg.hidden

    def w(*shape, scale=None):
        s = scale if scale is not None else (1.0 / math.sqrt(shape[0]))
        return jnp.asarray(rng.standard_normal(shape) * s, cfg.dtype)

    layers = []
    for _ in range(cfg.layers):
        layers.append({
            "wqkv": w(h, 3 * h),
            "wo": w(h, h),
            "w_up": w(h, cfg.mlp_ratio * h),
            "w_down": w(cfg.mlp_ratio * h, h),
            # AdaLN conditioning projection. A TRAINED DiT zero-inits these
            # (AdaLN-zero) and learns them up; random init here keeps the
            # conditioning path live so prompt/timestep actually modulate
            # the random-weight model (a loaded checkpoint replaces all of
            # this via the same pytree)
            "ada": w(h, 6 * h, scale=0.02),
            "ada_b": jnp.zeros((6 * h,), cfg.dtype),
        })
    return {
        "patch_in": w(cfg.patch_dim, h),
        "pos": w(cfg.num_patches, h, scale=0.02),
        "cond_embed": w(cfg.cond_vocab, h, scale=0.02),
        "t_mlp1": w(h, h),
        "t_mlp2": w(h, h),
        "final_ada": w(h, 2 * h, scale=0.02),
        "final_out": w(h, cfg.patch_dim, scale=0.02),
        "layers": layers,
    }


def _timestep_embed(t: jax.Array, dim: int) -> jax.Array:
    """Sinusoidal [B] -> [B, dim] (standard DDPM embedding)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _ln(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    m = x.mean(-1, keepdims=True)
    v = x.var(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-6)


def forward(
    params: Dict[str, Any], cfg: DiffusionConfig,
    x_t: jax.Array,        # [B, H, W, 3] noisy image, f32
    t: jax.Array,          # [B] int32 timestep
    cond_ids: jax.Array,   # [B, cond_len] int32 hashed prompt ids
) -> jax.Array:
    """Predict the noise eps for x_t. One fused transformer pass."""
    B = x_t.shape[0]
    p, n_side = cfg.patch_size, cfg.image_size // cfg.patch_size
    # patchify: [B, H, W, 3] -> [B, N, p*p*3]
    x = x_t.reshape(B, n_side, p, n_side, p, 3).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, cfg.num_patches, cfg.patch_dim).astype(cfg.dtype)
    h = x @ params["patch_in"] + params["pos"][None]

    # conditioning vector: mean prompt embedding + timestep MLP
    c = params["cond_embed"][cond_ids].mean(axis=1)              # [B, h]
    te = _timestep_embed(t, cfg.hidden).astype(cfg.dtype)
    c = c + jax.nn.silu(te @ params["t_mlp1"]) @ params["t_mlp2"]

    nh, hd = cfg.heads, cfg.hidden // cfg.heads
    for lp in params["layers"]:
        ada = (c @ lp["ada"] + lp["ada_b"]).astype(jnp.float32)
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(ada, 6, axis=-1)
        # attention with AdaLN-zero modulation
        u = (_ln(h) * (1 + sc1[:, None]) + sh1[:, None]).astype(cfg.dtype)
        qkv = u @ lp["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, -1, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, -1, nh, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, -1, nh, hd).transpose(0, 2, 1, 3)
        s = (q.astype(jnp.float32) @ k.astype(jnp.float32).transpose(0, 1, 3, 2))
        s = s / math.sqrt(hd)
        a = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        o = (a @ v).transpose(0, 2, 1, 3).reshape(B, -1, cfg.hidden)
        h = h + g1[:, None].astype(cfg.dtype) * (o @ lp["wo"])
        # MLP
        u = (_ln(h) * (1 + sc2[:, None]) + sh2[:, None]).astype(cfg.dtype)
        m = jax.nn.silu(u @ lp["w_up"]) @ lp["w_down"]
        h = h + g2[:, None].astype(cfg.dtype) * m

    ada = (c @ params["final_ada"]).astype(jnp.float32)
    sh, sc = jnp.split(ada, 2, axis=-1)
    u = (_ln(h) * (1 + sc[:, None]) + sh[:, None]).astype(cfg.dtype)
    out = u @ params["final_out"]                                # [B, N, pd]
    # unpatchify
    out = out.reshape(B, n_side, n_side, p, p, 3).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(B, cfg.image_size, cfg.image_size, 3).astype(jnp.float32)


def make_sampler(params: Dict[str, Any], cfg: DiffusionConfig):
    """Returns a jitted DDIM sampler: (key, cond_ids [B, L]) -> [B, H, W, 3]
    in [0, 1]. The whole denoise loop is one XLA program (lax.fori_loop)."""
    T = 1000
    betas = jnp.linspace(1e-4, 0.02, T, dtype=jnp.float32)
    alphas_bar = jnp.cumprod(1.0 - betas)
    # DDIM schedule: cfg.steps evenly spaced timesteps, high -> low
    ts = jnp.linspace(T - 1, 0, cfg.steps).astype(jnp.int32)

    def sample(key: jax.Array, cond_ids: jax.Array) -> jax.Array:
        B = cond_ids.shape[0]
        x = jax.random.normal(
            key, (B, cfg.image_size, cfg.image_size, 3), jnp.float32
        )

        def body(i, x):
            t = ts[i]
            t_next = jnp.where(i + 1 < cfg.steps, ts[jnp.minimum(i + 1, cfg.steps - 1)], -1)
            ab_t = alphas_bar[t]
            ab_next = jnp.where(t_next >= 0, alphas_bar[jnp.maximum(t_next, 0)], 1.0)
            eps = forward(params, cfg, x, jnp.full((B,), t, jnp.int32), cond_ids)
            x0 = (x - jnp.sqrt(1.0 - ab_t) * eps) / jnp.sqrt(ab_t)
            x0 = jnp.clip(x0, -3.0, 3.0)
            return jnp.sqrt(ab_next) * x0 + jnp.sqrt(1.0 - ab_next) * eps

        x = jax.lax.fori_loop(0, cfg.steps, body, x)
        return jnp.clip(x * 0.5 + 0.5, 0.0, 1.0)

    return jax.jit(sample)


def hash_prompt(prompt: str, cfg: DiffusionConfig) -> np.ndarray:
    """Prompt -> [cond_len] stable conditioning ids (FNV-1a over words;
    deterministic across processes — unlike hash())."""
    ids = np.zeros(cfg.cond_len, np.int32)
    words = (prompt.lower().split() or ["-"])[: cfg.cond_len]
    for i, word in enumerate(words):
        acc = 2166136261
        for b in word.encode():
            acc = ((acc ^ b) * 16777619) & 0xFFFFFFFF
        ids[i] = acc % cfg.cond_vocab
    return ids


def encode_png(img: np.ndarray) -> bytes:
    """[H, W, 3] float [0,1] or uint8 -> PNG bytes. Stdlib-only encoder
    (zlib + struct): zero-egress images ship no PIL."""
    import struct
    import zlib

    if img.dtype != np.uint8:
        img = (np.clip(img, 0.0, 1.0) * 255).astype(np.uint8)
    h, w, _ = img.shape
    raw = b"".join(b"\x00" + img[i].tobytes() for i in range(h))

    def chunk(tag: bytes, data: bytes) -> bytes:
        return (
            struct.pack(">I", len(data)) + tag + data
            + struct.pack(">I", zlib.crc32(tag + data) & 0xFFFFFFFF)
        )

    ihdr = struct.pack(">IIBBBBB", w, h, 8, 2, 0, 0, 0)
    return (
        b"\x89PNG\r\n\x1a\n"
        + chunk(b"IHDR", ihdr)
        + chunk(b"IDAT", zlib.compress(raw, 6))
        + chunk(b"IEND", b"")
    )
