"""Llama-family transformer in functional JAX (covers Llama 2/3, Qwen 2/3,
Mistral, DeepSeek-distill dense layouts via config switches).

Design notes (TPU-first):
- Pure param-pytree + functions: shardings are NamedSharding annotations on
  the pytree, jit handles the rest (psum inserted by XLA for row-parallel
  matmuls when inputs/outputs are sharded per parallel/mesh.py specs).
- Weights in bfloat16 (MXU native); attention logits and softmax in float32.
- Layers are a Python-level loop (unrolled under jit): no data-dependent
  control flow, static shapes everywhere.
- Attention is pluggable: callers pass an ``attend`` function so the same
  block stack serves contiguous prefill, paged decode, and ring/SP variants
  (see ops/attention.py).

The reference treats models as engine-internal (vLLM/SGLang own them); here
the model is first-class framework code.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 512
    hidden_size: int = 256
    num_layers: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    intermediate_size: int = 688
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_position: int = 8192
    qkv_bias: bool = False          # Qwen2-style
    qk_norm: bool = False           # Qwen3-style per-head q/k RMSNorm
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-scale config (byte tokenizer vocab)."""
        return cls(**kw)

    @classmethod
    def llama3_8b(cls, vocab_size: int = 128256) -> "LlamaConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=4096, num_layers=32, num_heads=32,
            num_kv_heads=8, head_dim=128, intermediate_size=14336,
            rope_theta=500000.0, max_position=8192, tie_embeddings=False,
        )

    @classmethod
    def llama3_70b(cls, vocab_size: int = 128256) -> "LlamaConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=8192, num_layers=80, num_heads=64,
            num_kv_heads=8, head_dim=128, intermediate_size=28672,
            rope_theta=500000.0, max_position=8192, tie_embeddings=False,
        )

    @classmethod
    def qwen3_0_6b(cls, vocab_size: int = 151936) -> "LlamaConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=1024, num_layers=28, num_heads=16,
            num_kv_heads=8, head_dim=128, intermediate_size=3072,
            rope_theta=1000000.0, qk_norm=True, tie_embeddings=True,
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    k = jax.random.split(rng, 8)
    h, qd, kvd, inter = cfg.hidden_size, cfg.q_size, cfg.kv_size, cfg.intermediate_size
    scale = 1.0 / math.sqrt(h)
    iscale = 1.0 / math.sqrt(inter)
    p: Params = {
        "attn_norm": jnp.ones((h,), cfg.dtype),
        "mlp_norm": jnp.ones((h,), cfg.dtype),
        "wq": (jax.random.normal(k[0], (h, qd)) * scale).astype(cfg.dtype),
        "wk": (jax.random.normal(k[1], (h, kvd)) * scale).astype(cfg.dtype),
        "wv": (jax.random.normal(k[2], (h, kvd)) * scale).astype(cfg.dtype),
        "wo": (jax.random.normal(k[3], (qd, h)) * scale).astype(cfg.dtype),
        "w_gate": (jax.random.normal(k[4], (h, inter)) * scale).astype(cfg.dtype),
        "w_up": (jax.random.normal(k[5], (h, inter)) * scale).astype(cfg.dtype),
        "w_down": (jax.random.normal(k[6], (inter, h)) * iscale).astype(cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), cfg.dtype)
        p["bk"] = jnp.zeros((kvd,), cfg.dtype)
        p["bv"] = jnp.zeros((kvd,), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), cfg.dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), cfg.dtype)
    return p


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    params: Params = {
        "embed": (
            jax.random.normal(keys[0], (cfg.vocab_size, cfg.hidden_size)) * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.dtype),
        "layers": [init_layer_params(keys[i + 2], cfg) for i in range(cfg.num_layers)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[1], (cfg.hidden_size, cfg.vocab_size)) * 0.02
        ).astype(cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def rope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float
) -> Tuple[jax.Array, jax.Array]:
    """positions [...,] -> cos/sin [..., head_dim//2] (float32)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(
    head_dim: int,
    theta: float,
    factor: float,
    original_max_position: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    truncate: bool = True,
) -> Tuple[jax.Array, float]:
    """(inv_freq [d/2], default attention_factor) per the YaRN recipe
    (transformers _compute_yarn_parameters semantics: interpolated and
    extrapolated frequencies blended over a linear ramp between the
    beta_fast/beta_slow correction dims; cos/sin scaled by
    0.1*ln(factor)+1 unless the config states its own attention_factor).
    ``factor <= 1`` is plain rope. The one copy: gpt-oss and the windowed
    MoE family (models/gptoss.py, models/moe.py) both read it."""
    d = head_dim
    pos_freqs = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    inv_extra = 1.0 / pos_freqs
    if factor <= 1.0:
        return inv_extra, 1.0
    inv_interp = 1.0 / (factor * pos_freqs)

    def corr_dim(rot):
        return (d * math.log(original_max_position / (rot * 2 * math.pi))) / (
            2 * math.log(theta)
        )

    low, high = corr_dim(beta_fast), corr_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    extra_factor = 1.0 - ramp
    inv_freq = inv_interp * (1 - extra_factor) + inv_extra * extra_factor
    return inv_freq, 0.1 * math.log(factor) + 1.0


def window_for_kind(kind: str, sliding_window: Optional[int]) -> Optional[int]:
    """A layer's sliding-window bound from its ``layer_types`` entry
    ("sliding_attention"/"sliding" against "full_attention"/"full"): the one
    rule the windowed families (gpt-oss, gemma, the windowed MoE) share.
    ``None`` = causal over the whole context."""
    return sliding_window if kind.startswith("sliding") else None


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., n_heads, head_dim], cos/sin broadcastable [..., 1, head_dim//2].

    Uses the "rotate-half" layout matching HF Llama (first/second half pairs),
    so HF checkpoints load without permutation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = x1f * cos - x2f * sin
    out2 = x2f * cos + x1f * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# attend(q, k_new, v_new, layer_idx) -> attention output [..., n_heads, head_dim]
AttendFn = Callable[[jax.Array, jax.Array, jax.Array, int], jax.Array]


def layer_forward(
    p: Params,
    cfg: LlamaConfig,
    x: jax.Array,                 # [..., S, hidden]
    cos: jax.Array,
    sin: jax.Array,
    attend: AttendFn,
    layer_idx: int,
    lora: Optional[Callable] = None,
) -> jax.Array:
    # optional batched LoRA (lora/adapters.py make_lora_fn): delta added to
    # a projection's output; returns None for targets without adapters
    def _lora(name: str, inp: jax.Array, out: jax.Array) -> jax.Array:
        if lora is None:
            return out
        delta = lora(name, layer_idx, inp)
        return out if delta is None else out + delta

    # attention
    h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    q = _lora("wq", h, h @ p["wq"])
    k = _lora("wk", h, h @ p["wk"])
    v = _lora("wv", h, h @ p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    new_shape = h.shape[:-1]
    q = q.reshape(*new_shape, cfg.num_heads, cfg.head_dim)
    k = k.reshape(*new_shape, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(*new_shape, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn_out = attend(q, k, v, layer_idx)
    attn_out = attn_out.reshape(*new_shape, cfg.q_size)
    x = x + _lora("wo", attn_out, attn_out @ p["wo"])
    # mlp
    h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(
        (_lora("w_gate", h, h @ p["w_gate"])).astype(jnp.float32)
    ).astype(x.dtype)
    up = _lora("w_up", h, h @ p["w_up"])
    gu = gate * up
    x = x + _lora("w_down", gu, gu @ p["w_down"])
    return x


def forward(
    params: Params,
    cfg: LlamaConfig,
    token_ids: jax.Array,        # [..., S] int32
    positions: jax.Array,        # [..., S] int32
    attend: AttendFn,
    lora: Optional[Callable] = None,
    inputs_embeds: Optional[jax.Array] = None,  # [..., S, hidden]
) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden] (pre-lm_head).

    ``inputs_embeds`` replaces the embedding gather when given — the
    multimodal path splices vision soft tokens in (models/vision.py)."""
    x = params["embed"][token_ids] if inputs_embeds is None else inputs_embeds
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]  # broadcast over heads
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(layer, cfg, x, cos, sin, attend, i, lora=lora)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: LlamaConfig, hidden: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return (hidden @ params["embed"].T).astype(jnp.float32)
    return (hidden @ params["lm_head"]).astype(jnp.float32)
