"""Command A+ (``model_type`` ``cohere2_moe``): a PARALLEL block under one
mean-centred LayerNorm. A layer reads ``u = LN(x)`` once; grouped-query
attention and the routed feed-forward both read ``u`` and the residual takes
both in one sum, ``x + attend(u) + experts(u)``. Three layers in four attend
inside a sliding window with rotary positions (published in the interleaved
GPT-J layout; the engine keeps its rotate-half ``apply_rope`` and the weights
are de-interleaved a head at load, engine/weights.py); the fourth attends
every causal key and has NO positions at all. The feed-forward is the routed
one of ``models/moe.py`` (``routed_shared_ffn``: sigmoid scores, no selection
bias, normalised top-k) beside ``num_shared_experts`` shared experts whose
outputs are AVERAGED: one SwiGLU as wide as all of them, its sum scaled by
``shared_expert_scale``.

Pages are kept BY LAYER KIND (``page_groups``): the full layers' pages live as
long as their request, the sliding layers' one window, in pools and block
tables of their own (engine/engine.py, docs/architecture.md "Pages by layer
kind").

What the public configuration does not fix is chosen here and listed, each
with its alternative, in ``benchmarks/configs/command-a-plus-ep8-d4.json``
(``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import moe as moelib
from .llama import Params, apply_rope, rope_cos_sin, window_for_kind

F32 = jnp.float32
EXPERT_STACKS = moelib.ROUTED_SHARED_STACKS


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 512                 # as HELD (a slice of the published)
    hidden_size: int = 64
    num_layers: int = 4                   # layers held
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 16
    # a layer's kind, the public config.json's spelling; the period is
    # sliding x 3 + full (``order_of_interleaved_layers`` local_attn_first)
    layer_types: Tuple[str, ...] = (
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention",
    )
    sliding_window: int = 64              # keys a query reads, its own among them
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    max_position: int = 8192
    tie_embeddings: bool = True
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    # the routed feed-forward (the names models/moe.py routed_shared_ffn reads)
    num_experts: int = 16
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 32       # ONE expert's width, shared ones too
    norm_topk_prob: bool = True
    moe_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 2
    # "average": the shared experts' mean is added to the routed sum
    # ("sum": their sum unscaled)
    shared_expert_combination: str = "average"
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the experts this chip holds of every layer
    experts_held: Optional[Tuple[int, int]] = None
    # the name the shared SwiGLU has in a device trace (routed_shared_ffn)
    shared_scope: str = "cmda_shared"

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers:
            raise ValueError("layer_types names every layer that is held")
        if self.shared_expert_combination not in ("average", "sum"):
            raise ValueError(
                f"shared_expert_combination {self.shared_expert_combination!r}"
            )

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def shared_expert_scale(self) -> float:
        """What the shared SwiGLU's sum is scaled by (routed_shared_ffn)."""
        if self.shared_expert_combination == "average":
            return 1.0 / max(self.num_shared_experts, 1)
        return 1.0

    def window_for_layer(self, layer_idx: int) -> Optional[int]:
        return window_for_kind(self.layer_types[layer_idx], self.sliding_window)

    @classmethod
    def tiny(cls, **kw) -> "Cohere2MoeConfig":
        """Test scale that keeps the shape: one period (sliding x 3 + full),
        4 query heads a kv head, 16 experts top 4 beside 2 shared ones."""
        return cls(**kw)

    @classmethod
    def command_a_plus(cls, num_layers: int = 32, vocab_size: int = 262144,
                       experts_held: Optional[Tuple[int, int]] = None,
                       ) -> "Cohere2MoeConfig":
        """CohereLabs/command-a-plus-05-2026's config.json (the text path)."""
        period = ("sliding_attention",) * 3 + ("full_attention",)
        return cls(
            vocab_size=vocab_size, hidden_size=4096, num_layers=num_layers,
            num_heads=128, num_kv_heads=8, head_dim=128,
            layer_types=tuple(period[i % 4] for i in range(num_layers)),
            sliding_window=4096, rope_theta=50000.0, max_position=200000,
            num_experts=128, num_experts_per_tok=8, moe_intermediate_size=4096,
            num_shared_experts=4, experts_held=experts_held,
        )


def page_groups(cfg: Cohere2MoeConfig) -> Tuple[Tuple[Tuple[int, ...], Optional[int]], ...]:
    """(layers, lifetime) a group of page layers: the full layers' pages live
    as long as their request (``None``), the sliding layers' one window of
    positions. A configuration of one kind answers one group."""
    full = tuple(i for i in range(cfg.num_layers) if cfg.window_for_layer(i) is None)
    sliding = tuple(i for i in range(cfg.num_layers) if i not in full)
    groups = ((full, None), (sliding, cfg.sliding_window))
    return tuple(g for g in groups if g[0])


def read_counters(cfg: Cohere2MoeConfig) -> Tuple[str, ...]:
    """The ``StepStats`` fields ``forward`` adds to its ``stats``, in the
    order they ride a step's readback: the keys the step's real decode rows
    read in sliding and in full layers, and those rows, each summed over rows
    and the layers of its kind."""
    return ("win_keys_read", "full_keys_read", "win_decode_rows",
            "full_decode_rows")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _mat(key, fan_in: int, fan_out: int, dt) -> jax.Array:
    return (jax.random.normal(key, (fan_in, fan_out)) / math.sqrt(fan_in)).astype(dt)


def init_layer_params(rng: jax.Array, cfg: Cohere2MoeConfig) -> Params:
    """Every matrix at ``fan_in ** -0.5``: on a LayerNorm's output (unit
    variance) the attention branch, the routed sum (weights that add to 1)
    and the shared mean each come out near unit scale, so dropping a branch,
    the 1/4, the window or the rotation moves the logits by a large share of
    their size (the tests and ``--calibrate`` hold every such variant
    apart)."""
    k = jax.random.split(rng, 12)
    h, dt = cfg.hidden_size, cfg.dtype
    E, inter = cfg.num_experts, cfg.moe_intermediate_size
    held = E if cfg.experts_held is None else cfg.experts_held[1]
    scale, iscale = 1.0 / math.sqrt(h), 1.0 / math.sqrt(inter)
    si = inter * cfg.num_shared_experts
    return {
        "norm": jnp.ones((h,), dt),                       # no bias
        "wq": _mat(k[0], h, cfg.q_size, dt), "wk": _mat(k[1], h, cfg.kv_size, dt),
        "wv": _mat(k[2], h, cfg.kv_size, dt), "wo": _mat(k[3], cfg.q_size, h, dt),
        "w_router": _mat(k[4], h, E, dt),                 # no selection bias
        "w_egate": (jax.random.normal(k[5], (held, h, inter)) * scale).astype(dt),
        "w_eup": (jax.random.normal(k[6], (held, h, inter)) * scale).astype(dt),
        "w_edown": (jax.random.normal(k[7], (held, inter, h)) * iscale).astype(dt),
        # the shared experts side by side: one SwiGLU of their summed width
        "w_shared_gate": _mat(k[8], h, si, dt),
        "w_shared_up": _mat(k[9], h, si, dt),
        "w_shared_down": (jax.random.normal(k[10], (si, h)) * iscale).astype(dt),
    }


def init_params(rng: jax.Array, cfg: Cohere2MoeConfig) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    h = cfg.hidden_size
    params: Params = {
        # the head is the embedding (tied) on a LayerNorm's output: rows at
        # h ** -0.5 make the logits of unit scale, a distribution with
        # entropy and not one token at probability 1, which no error moves
        "embed": _mat(keys[0], h, cfg.vocab_size, cfg.dtype).T,
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": [init_layer_params(keys[i + 2], cfg) for i in range(cfg.num_layers)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _mat(keys[1], h, cfg.vocab_size, cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def layer_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Mean-centred LayerNorm with a weight and no bias, in float32 (beside
    ``llama.rms_norm``, which does not remove the mean)."""
    dtype = x.dtype
    x32 = x.astype(F32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def layer_forward(p: Params, cfg: Cohere2MoeConfig, x: jax.Array, rope,
                  attend, layer_idx: int, stats=None,
                  matmul=moelib.grouped_matmul_reference) -> jax.Array:
    """One parallel block: ``u`` once, both branches read it, one sum."""
    u = layer_norm(x, p["norm"], cfg.layer_norm_eps)
    lead = u.shape[:-1]
    with jax.named_scope("cmda_attend"):
        q = (u @ p["wq"]).reshape(*lead, cfg.num_heads, cfg.head_dim)
        k = (u @ p["wk"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
        v = (u @ p["wv"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
        window = cfg.window_for_layer(layer_idx)
        if window is None:
            a = attend(q, k, v, layer_idx)                # no positions at all
        else:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
            a = attend(q, k, v, layer_idx, window=window)
        a = a.reshape(*lead, cfg.q_size) @ p["wo"]
    with jax.named_scope("cmda_experts"):
        # routing indexes per token: flatten leading dims to [T, H]
        f = moelib.routed_shared_ffn(
            p, cfg, u.reshape(-1, u.shape[-1]), stats=stats, matmul=matmul
        )
    return x + a + f.reshape(u.shape)


def forward(params: Params, cfg: Cohere2MoeConfig, token_ids: jax.Array,
            positions: jax.Array, attend, stats=None,
            matmul=moelib.grouped_matmul_reference,
            lora: Optional[Callable] = None) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden]. ``stats``
    (moe.RoutingStats): the grouped expert path counts its routing into it."""
    if lora is not None:
        raise NotImplementedError("LoRA is not supported for the cohere2_moe family")
    x = params["embed"][token_ids]
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    rope = (cos[..., None, :], sin[..., None, :])
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(layer, cfg, x, rope, attend, i, stats=stats, matmul=matmul)
    if stats is not None:
        n_full = sum(cfg.window_for_layer(i) is None for i in range(cfg.num_layers))
        n_win = cfg.num_layers - n_full
        rows = stats.decode_rows.reshape(-1)
        keys = jnp.where(rows, positions.reshape(-1) + 1, 0)
        stats.add_reads(
            win_keys_read=jnp.minimum(keys, cfg.sliding_window).sum() * n_win,
            full_keys_read=keys.sum() * n_full,
            win_decode_rows=rows.sum() * n_win,
            full_decode_rows=rows.sum() * n_full,
        )
    return layer_norm(x, params["final_norm"], cfg.layer_norm_eps)


def lm_logits(params: Params, cfg: Cohere2MoeConfig, hidden: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (hidden @ w).astype(F32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale
