"""EvaByte: a byte-level decoder whose every layer attends EXACTLY inside the
query's own window and to ONE LEARNED SUMMARY a chunk of every window before
it (``model_type`` ``evabyte``, ``attention_class`` ``eva``: "Efficient
Attention via Control Variates", arXiv 2302.04542).

One layer, a head ``h`` (``d`` lanes, ``s = d^-0.5``, window ``W``, chunk
``C``), on rotated keys:

- chunk ``c`` (positions ``[cC, cC + C)``) is summarised from its own keys
  and values alone, with the head's two learned vectors ``mu`` and ``phi``:
  ``k~_c = sum_j softmax_j(mu . k_j) k_j``,
  ``v~_c = sum_j softmax_j(phi . k_j - |k_j|^2 / 2) v_j`` (float32);
- query ``n`` in window ``w = n // W`` takes ONE softmax over the keys ``m <=
  n`` of its own window and the summaries of every chunk of the windows
  before ``w`` (a chunk counts as one key), nothing of its own window's.

So the state of a request is of a THIRD kind (docs/architecture.md): pages
that live one window (a ring of ``W / page`` pages, position ``p`` in entry
``(p mod W) // page``: the next window overwrites them) and summaries by
window that live as long as the request. The forward stays a pure function
of ``(params, tokens, positions, attend)``: a layer hands the seam an
``EvaQuery`` (ops/attention.py) with its two vectors, and who owns the ring
and the summaries decides what ``attend`` does with it
(ops/paged_attention.py for the engine; ``stateless_attend`` below for a
whole sequence from nothing).

Everything outside the attention call is the dense family's
(models/llama.py: the norm, the rotary tables, SwiGLU) with the
publication's switches: the norms' weights are ``1 + w``
(``norm_add_unit_offset``), the residual stream is float32
(``fp32_skip_add``), the logits float32 (``fp32_logits``), the head has
``num_pred_heads`` x vocabulary outputs of which the served path samples
head 0 (the next byte; heads 1-7 are held and not run).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as att
from .llama import AttendFn, Params, apply_rope, rms_norm, rope_cos_sin

F32 = jnp.float32

# the scale ``mu`` and ``phi`` are drawn at (init_params): the chunk
# softmaxes' logits then have a spread of about 1.5 over a chunk's 16 keys,
# so that a summary leans on some keys and dropping either vector shows in
# the logits. The published ``init_std`` (0.01275) would leave both flat
# (a spread of 0.05: every summary the chunk's mean).
SUMMARY_VECTOR_STD = 0.4


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320                 # 256 bytes + 64 special ids
    hidden_size: int = 256
    num_layers: int = 2                   # layers held
    num_heads: int = 4
    num_kv_heads: int = 4                 # multi-head: as many as num_heads
    head_dim: int = 64
    intermediate_size: int = 688
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    max_position: int = 32768
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_kv_heads != self.num_heads:
            raise ValueError("EVA is multi-head: num_kv_heads == num_heads")
        if self.window_size % self.chunk_size:
            raise ValueError("a window is a whole number of chunks")

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.q_size

    @property
    def chunks_per_window(self) -> int:
        return self.window_size // self.chunk_size

    @classmethod
    def tiny(cls, **kw) -> "EvaByteConfig":
        """Test scale: 4 heads x 16, a window of 16 chunks (one page of
        summaries a window at a 16-token page)."""
        base = dict(
            hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
            head_dim=16, intermediate_size=128, window_size=256,
            chunk_size=16, max_position=2048,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def evabyte_6_5b(cls, **kw) -> "EvaByteConfig":
        base = dict(
            hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=32,
            head_dim=128, intermediate_size=11008,
        )
        base.update(kw)
        return cls(**base)


def window_ring(cfg: EvaByteConfig) -> int:
    """Positions a request's pages cover before they are written again."""
    return cfg.window_size


def summary_spec(cfg: EvaByteConfig) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
    """What ONE CLOSED WINDOW keeps a layer, as (name, shape, dtype): a
    summary key and a summary value a chunk a head, in the pages' dtype
    (written once, like a key)."""
    shape = (cfg.chunks_per_window, cfg.num_heads, cfg.head_dim)
    return (("k_summary", shape, cfg.dtype), ("v_summary", shape, cfg.dtype))


def read_counters(cfg: EvaByteConfig) -> Tuple[str, ...]:
    """The ``StepStats`` fields ``forward`` adds to its ``stats``, in the
    order they ride a step's readback: what the step's real decode rows read
    (summed over rows and layers), the windows they closed and the step."""
    return ("eva_rows_attended", "eva_window_keys", "eva_summaries_read",
            "eva_windows_closed", "eva_decode_steps")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer_params(rng: jax.Array, cfg: EvaByteConfig) -> Params:
    """Every matrix at ``1 / sqrt(fan_in)`` but the two that set the
    attention's temperature: keys at 0.35 x and queries at 2.8 x that, so
    that ``|k|^2 / 2`` (the second chunk softmax's own term) spreads by about
    1 over a chunk where unit keys would spread by 8 and make every ``v~``
    one value, while ``s q . k`` keeps a spread of 1. The norms' ``w`` are
    drawn about 0 (their weight is ``1 + w``)."""
    k = jax.random.split(rng, 11)
    h, qd, inter = cfg.hidden_size, cfg.q_size, cfg.intermediate_size
    scale, iscale = 1.0 / math.sqrt(h), 1.0 / math.sqrt(inter)

    def draw(key, shape, std):
        return (jax.random.normal(key, shape) * std).astype(cfg.dtype)

    return {
        "attn_norm": draw(k[0], (h,), 0.1),
        "mlp_norm": draw(k[1], (h,), 0.1),
        "wq": draw(k[2], (h, qd), 2.8 * scale),
        "wk": draw(k[3], (h, qd), 0.35 * scale),
        "wv": draw(k[4], (h, qd), scale),
        "wo": draw(k[5], (qd, h), 1.0 / math.sqrt(qd)),
        "mu": draw(k[6], (cfg.num_heads, cfg.head_dim), SUMMARY_VECTOR_STD),
        "phi": draw(k[7], (cfg.num_heads, cfg.head_dim), SUMMARY_VECTOR_STD),
        "w_gate": draw(k[8], (h, inter), scale),
        "w_up": draw(k[9], (h, inter), scale),
        "w_down": draw(k[10], (inter, h), iscale),
    }


def init_params(rng: jax.Array, cfg: EvaByteConfig) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 3)
    H, V = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": (jax.random.normal(keys[0], (V, H))).astype(cfg.dtype),
        "final_norm": (jax.random.normal(keys[1], (H,)) * 0.1).astype(cfg.dtype),
        # [hidden, heads of prediction x vocabulary]: head j's columns are
        # [j V, (j + 1) V)
        "lm_head": (
            jax.random.normal(keys[2], (H, cfg.num_pred_heads * V)) / math.sqrt(H)
        ).astype(cfg.dtype),
        "layers": [
            init_layer_params(keys[i + 3], cfg) for i in range(cfg.num_layers)
        ],
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _norm(x: jax.Array, w: jax.Array, cfg: EvaByteConfig) -> jax.Array:
    """RMSNorm of the float32 stream with the weight ``1 + w``, in the
    weights' dtype for the projections that follow."""
    return rms_norm(x, 1.0 + w.astype(F32), cfg.rms_norm_eps).astype(cfg.dtype)


def layer_forward(p: Params, cfg: EvaByteConfig, x: jax.Array, cos, sin,
                  attend: AttendFn, layer_idx: int) -> jax.Array:
    """``x`` [..., S, hidden] float32 (``fp32_skip_add``)."""
    u = _norm(x, p["attn_norm"], cfg)
    lead = u.shape[:-1]
    heads = (*lead, cfg.num_heads, cfg.head_dim)
    q = apply_rope((u @ p["wq"]).reshape(heads), cos, sin)
    k = apply_rope((u @ p["wk"]).reshape(heads), cos, sin)
    v = (u @ p["wv"]).reshape(heads)
    o = attend(
        q, k, v, layer_idx,
        eva=att.EvaQuery(p["mu"], p["phi"], cfg.window_size, cfg.chunk_size),
    )
    x = x + (o.reshape(*lead, cfg.q_size) @ p["wo"]).astype(F32)
    t = _norm(x, p["mlp_norm"], cfg)
    gate = jax.nn.silu((t @ p["w_gate"]).astype(F32)).astype(cfg.dtype)
    return x + ((gate * (t @ p["w_up"])) @ p["w_down"]).astype(F32)


def forward(params: Params, cfg: EvaByteConfig, token_ids: jax.Array,
            positions: jax.Array, attend: AttendFn, stats=None) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden] in the weights'
    dtype. ``stats`` (models/moe.RoutingStats, used for its readback alone):
    what the step's real decode rows read, under ``read_counters``' names."""
    x = params["embed"][token_ids].astype(F32)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(layer, cfg, x, cos, sin, attend, i)
    if stats is not None:
        W, L = cfg.window_size, cfg.num_layers
        rows = stats.decode_rows.reshape(-1)
        p = positions.reshape(-1)
        stats.add_reads(
            eva_rows_attended=rows.sum() * L,
            eva_window_keys=jnp.where(rows, p % W + 1, 0).sum() * L,
            eva_summaries_read=(
                jnp.where(rows, p // W, 0).sum() * cfg.chunks_per_window * L
            ),
            # a row at a window's first position: the window before closed
            eva_windows_closed=(rows & (p % W == 0) & (p > 0)).sum(),
            eva_decode_steps=jnp.ones((), jnp.int32),
        )
    return _norm(x, params["final_norm"], cfg)


def lm_logits(params: Params, cfg: EvaByteConfig, hidden: jax.Array) -> jax.Array:
    """Prediction head 0 of ``num_pred_heads`` (the next byte), float32."""
    head0 = params["lm_head"][:, : cfg.vocab_size]
    return jnp.matmul(hidden, head0, preferred_element_type=F32)


def stateless_attend(cfg: EvaByteConfig):
    """``attend`` over ONE whole sequence from nothing (``q`` [S, h, d], S a
    whole number of chunks): the pure-JAX twin of the family's attention
    with no pages, no ring and no store (ops/attention.eva_attention)."""

    def attend(q, k, v, layer_idx, eva: Optional[att.EvaQuery] = None):
        return att.eva_attention(q, k, v, eva)

    return attend
