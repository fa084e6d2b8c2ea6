"""Gemma 2 / Gemma 3 (text) family.

The reference serves Gemma through its engine adapters; this engine owns
the model, so the family lives here like llama/moe/mla/gptoss. What makes
Gemma not-llama (all verified against the HF reference implementations,
transformers models/gemma2/modeling_gemma2.py and gemma3/modeling_gemma3.py,
and pinned by tests/test_gemma_parity.py):

- RMSNorm computes in float32 and scales by (1 + weight) — the zero-init
  convention (Gemma2RMSNorm.forward).
- embeddings are scaled by sqrt(hidden_size) CAST TO THE MODEL DTYPE first
  (the HF "normalizer" downcast quirk — sqrt(3072) becomes 55.5 in bf16;
  parity requires reproducing it).
- sandwich norms: post_attention_layernorm wraps the attention OUTPUT and
  post_feedforward_layernorm wraps the MLP output, in addition to the
  usual pre-norms.
- attention scale is query_pre_attn_scalar**-0.5, not head_dim**-0.5
  (implemented by pre-scaling q so the attention ops stay unchanged).
- interleaved sliding-window / full attention per layer_types, riding the
  same paged ``window`` machinery as gpt-oss (ops/attention.py).
- Gemma 2: attention-logit softcapping (tanh) inside the score matrix
  (ops/attention.py ``softcap``) and final-logit softcapping in lm_logits.
- Gemma 3: per-head q/k RMSNorm (Gemma convention), no softcaps, and DUAL
  rope — sliding layers use rope_local_base_freq, full layers use
  rope_theta with an optional linear position scale (factor 8 on the
  released checkpoints).
- GeGLU MLP: gelu_tanh(gate) * up.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .llama import AttendFn, LlamaConfig, Params, window_for_kind


@dataclasses.dataclass(frozen=True)
class GemmaConfig(LlamaConfig):
    query_pre_attn_scalar: float = 256.0
    sliding_window: int = 4096
    # per-layer kinds: "sliding" | "full"; () = derive from sliding_pattern
    layer_types: Tuple[str, ...] = ()
    # every Nth layer is full attention (gemma2: 2 -> alternate, full on
    # odd; gemma3: 6 -> five sliding then one full)
    sliding_pattern: int = 2
    attn_logit_softcap: Optional[float] = None   # gemma2: 50.0
    final_logit_softcap: Optional[float] = None  # gemma2: 30.0
    # gemma3 dual rope: sliding layers use the local theta (no scaling),
    # full layers use rope_theta / linear factor
    rope_local_theta: Optional[float] = None
    rope_scaling_factor: float = 1.0

    def kind_for_layer(self, layer_idx: int) -> str:
        if self.layer_types:
            return self.layer_types[layer_idx]
        # HF convention for both families: layer_idx+1 % pattern == 0 ->
        # full ("sliding_attention" otherwise)
        return "full" if (layer_idx + 1) % self.sliding_pattern == 0 else "sliding"

    def window_for_layer(self, layer_idx: int) -> Optional[int]:
        return window_for_kind(self.kind_for_layer(layer_idx), self.sliding_window)

    @classmethod
    def tiny_gemma2(cls, **kw) -> "GemmaConfig":
        defaults = dict(
            vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128,
            query_pre_attn_scalar=16.0, sliding_window=16,
            attn_logit_softcap=50.0, final_logit_softcap=30.0,
            tie_embeddings=True, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny_gemma3(cls, **kw) -> "GemmaConfig":
        defaults = dict(
            vocab_size=512, hidden_size=64, num_layers=6, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128,
            query_pre_attn_scalar=16.0, sliding_window=16,
            sliding_pattern=3, qk_norm=True, rope_theta=1_000_000.0,
            rope_local_theta=10_000.0, rope_scaling_factor=8.0,
            tie_embeddings=True, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def gemma2_2b(cls, vocab_size: int = 256000) -> "GemmaConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=2304, num_layers=26,
            num_heads=8, num_kv_heads=4, head_dim=256,
            intermediate_size=9216, query_pre_attn_scalar=256.0,
            sliding_window=4096, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, tie_embeddings=True,
            max_position=8192,
        )

    @classmethod
    def gemma3_4b(cls, vocab_size: int = 262208) -> "GemmaConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=2560, num_layers=34,
            num_heads=8, num_kv_heads=4, head_dim=256,
            intermediate_size=10240, query_pre_attn_scalar=256.0,
            sliding_window=1024, sliding_pattern=6, qk_norm=True,
            rope_theta=1_000_000.0, rope_local_theta=10_000.0,
            rope_scaling_factor=8.0, tie_embeddings=True,
            max_position=131072,
        )


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def gemma_rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Gemma convention: float32 math, scale by (1 + weight) BEFORE the
    downcast ((x*w).to(dtype), not x.to(dtype)*w — HF PR #29402)."""
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# rope: the HF rotate-half layout is exactly llama's — reuse those helpers.
# Gemma3's linear position scale on full-attention layers folds into the
# positions BEFORE the table build (positions / factor).
from .llama import apply_rope, rope_cos_sin  # noqa: E402


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer_params(rng: jax.Array, cfg: GemmaConfig) -> Params:
    k = jax.random.split(rng, 7)
    h, qd, kvd = cfg.hidden_size, cfg.q_size, cfg.kv_size
    inter = cfg.intermediate_size
    scale = 1.0 / math.sqrt(h)
    p: Params = {
        "attn_norm": jnp.zeros((h,), cfg.dtype),
        "post_attn_norm": jnp.zeros((h,), cfg.dtype),
        "pre_mlp_norm": jnp.zeros((h,), cfg.dtype),
        "post_mlp_norm": jnp.zeros((h,), cfg.dtype),
        "wq": (jax.random.normal(k[0], (h, qd)) * scale).astype(cfg.dtype),
        "wk": (jax.random.normal(k[1], (h, kvd)) * scale).astype(cfg.dtype),
        "wv": (jax.random.normal(k[2], (h, kvd)) * scale).astype(cfg.dtype),
        "wo": (jax.random.normal(k[3], (qd, h)) * scale).astype(cfg.dtype),
        "w_gate": (jax.random.normal(k[4], (h, inter)) * scale).astype(cfg.dtype),
        "w_up": (jax.random.normal(k[5], (h, inter)) * scale).astype(cfg.dtype),
        "w_down": (jax.random.normal(k[6], (inter, h)) * (1.0 / math.sqrt(inter))).astype(cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), cfg.dtype)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), cfg.dtype)
    return p


def init_params(rng: jax.Array, cfg: GemmaConfig) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    params: Params = {
        "embed": (
            jax.random.normal(keys[0], (cfg.vocab_size, cfg.hidden_size)) * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.zeros((cfg.hidden_size,), cfg.dtype),
        "layers": [
            init_layer_params(keys[i + 2], cfg) for i in range(cfg.num_layers)
        ],
    }
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def layer_forward(
    p: Params,
    cfg: GemmaConfig,
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    attend: AttendFn,
    layer_idx: int,
) -> jax.Array:
    lead = x.shape[:-1]
    h = gemma_rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    q = (h @ p["wq"]).reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:  # gemma3 per-head norms, gemma convention
        q = gemma_rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = gemma_rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # the attention ops scale by head_dim**-0.5; gemma wants
    # query_pre_attn_scalar**-0.5 — fold the ratio into q
    q = q * jnp.asarray(
        math.sqrt(cfg.head_dim) / math.sqrt(cfg.query_pre_attn_scalar),
        q.dtype,
    )
    attn = attend(
        q, k, v, layer_idx,
        window=cfg.window_for_layer(layer_idx),
        softcap=cfg.attn_logit_softcap,
    )
    attn = attn.reshape(*lead, cfg.q_size) @ p["wo"]
    x = x + gemma_rms_norm(attn, p["post_attn_norm"], cfg.rms_norm_eps)

    h2 = gemma_rms_norm(x, p["pre_mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.gelu(
        (h2 @ p["w_gate"]).astype(jnp.float32), approximate=True
    ).astype(x.dtype)
    mlp = (gate * (h2 @ p["w_up"])) @ p["w_down"]
    return x + gemma_rms_norm(mlp, p["post_mlp_norm"], cfg.rms_norm_eps)


def forward(
    params: Params,
    cfg: GemmaConfig,
    token_ids: jax.Array,
    positions: jax.Array,
    attend: AttendFn,
    lora: Optional[Callable] = None,
    inputs_embeds: Optional[jax.Array] = None,
) -> jax.Array:
    if lora is not None:
        raise NotImplementedError("LoRA is not supported for the gemma family")
    x = params["embed"][token_ids] if inputs_embeds is None else inputs_embeds
    # the HF normalizer downcast quirk is part of the checkpoint contract
    x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
    tables = {}

    def rope_for(layer_idx: int):
        if cfg.rope_local_theta is None:
            key = ("global",)
            theta, scale = cfg.rope_theta, 1.0
        elif cfg.kind_for_layer(layer_idx) == "sliding":
            key = ("local",)
            theta, scale = cfg.rope_local_theta, 1.0
        else:
            key = ("global",)
            theta, scale = cfg.rope_theta, cfg.rope_scaling_factor
        if key not in tables:
            cos, sin = rope_cos_sin(
                positions.astype(jnp.float32) / scale, cfg.head_dim, theta
            )
            tables[key] = (cos[..., None, :], sin[..., None, :])
        return tables[key]

    for i, layer in enumerate(params["layers"]):
        cos, sin = rope_for(i)
        x = layer_forward(layer, cfg, x, cos, sin, attend, i)
    return gemma_rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: GemmaConfig, hidden: jax.Array) -> jax.Array:
    head = params.get("lm_head")  # untied finetunes; released gemma ties
    logits = (
        hidden @ head if head is not None else hidden @ params["embed"].T
    ).astype(jnp.float32)
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits
