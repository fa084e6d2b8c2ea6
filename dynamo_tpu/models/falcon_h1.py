"""Falcon-H1: a PARALLEL hybrid. Every layer feeds one normalised input to a
Mamba-2 state-space mixer and to grouped-query attention, adds both outputs
to the residual together, then a gated MLP (``model_type`` ``falcon_h1``).

Two kinds of state. Attention keeps pages of keys and values behind
``attend`` like every other family. The mixer keeps, for each request, a
recurrent state ``[heads, state, head_dim]`` in float32 (transposed:
ops/pallas_ssm.py says why) and the last ``conv - 1`` inputs of its causal
convolution: both behind the second seam,
``mix(xBC, dt, layer) -> y`` (the channels before their convolution and the
raw step sizes in, the heads' outputs before the gate out). The forward
stays a pure function of ``(params, tokens, positions, attend, mix)``; who
owns the state decides what ``mix`` is (``mix_chunk`` over a run of tokens
from a given state, ``mix_rows`` for one token a row: the engine's step
programs build theirs from these two, ``stateless_mix`` is a whole sequence
from zeros).

The publication's multipliers (muP) are data on the configuration and are
applied where transformers' ``modeling_falcon_h1`` applies them. They shrink
every branch, so ``init_params`` draws each matrix with ``std = 1 /
(sqrt(fan_in) x the multipliers on its input and output)``: every branch's
output is then of order 1 and a dropped multiplier shows.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import pallas_ssm
from .llama import Params, apply_rope, rms_norm, rope_cos_sin

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 512                 # as HELD (a slice of the published)
    hidden_size: int = 256
    num_layers: int = 2                   # layers held
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    intermediate_size: int = 512
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    max_position: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # the mixer
    mamba_d_ssm: int = 256                # heads x head size
    mamba_n_heads: int = 4
    mamba_d_head: int = 64
    mamba_d_state: int = 32
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_norm_before_gate: bool = False
    # the multipliers, as published
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)  # z x B C dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)               # gate, down

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("the mixer's heads divide into mamba_n_groups")
        if self.mamba_norm_before_gate:
            raise ValueError("mamba_norm_before_gate is not run (Falcon-H1 gates first)")

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def bc_size(self) -> int:
        """Lanes of B (and of C): groups x state."""
        return self.mamba_n_groups * self.mamba_d_state

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: x | B | C."""
        return self.mamba_d_ssm + 2 * self.bc_size

    @property
    def in_proj_size(self) -> int:
        return 2 * self.mamba_d_ssm + 2 * self.bc_size + self.mamba_n_heads

    @property
    def in_proj_segments(self) -> Tuple[int, ...]:
        """Widths of W_inproj's output segments: z | x | B | C | dt."""
        return (self.mamba_d_ssm, self.mamba_d_ssm, self.bc_size,
                self.bc_size, self.mamba_n_heads)

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        """Test scale that keeps the shape's oddities: 5 query heads a kv
        head, 2 groups, a state wider than the head, every multiplier away
        from 1."""
        base = dict(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=10,
            num_kv_heads=2, head_dim=16, intermediate_size=256,
            mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=32, mamba_n_groups=2, mamba_chunk_size=8,
            embedding_multiplier=5.66, lm_head_multiplier=0.05,
            attention_in_multiplier=0.9, attention_out_multiplier=0.3,
            key_multiplier=0.2, ssm_in_multiplier=0.25, ssm_out_multiplier=0.4,
            ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.6),
            mlp_multipliers=(0.18, 0.11),
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def falcon_h1_34b(cls, num_layers: int = 72, vocab_size: int = 261120) -> "FalconH1Config":
        """tiiuae/Falcon-H1-34B-Instruct's config.json."""
        return cls(
            vocab_size=vocab_size, hidden_size=5120, num_layers=num_layers,
            num_heads=20, num_kv_heads=4, head_dim=128,
            intermediate_size=21504, rope_theta=1e11, rms_norm_eps=1e-5,
            max_position=262144, mamba_d_ssm=4096, mamba_n_heads=32,
            mamba_d_head=128, mamba_d_state=256, mamba_n_groups=2,
            mamba_d_conv=4, mamba_chunk_size=128,
            embedding_multiplier=5.656854249492381,
            lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
            attention_out_multiplier=0.0375,
            key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
            ssm_out_multiplier=0.08838834764831845,
            ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                             0.5, 0.3535533905932738),
            mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        )


def mup_vector(cfg: FalconH1Config) -> jax.Array:
    """``ssm_multipliers`` spread over W_inproj's output lanes, [in_proj_size]."""
    return jnp.concatenate([
        jnp.full((w,), m, F32)
        for w, m in zip(cfg.in_proj_segments, cfg.ssm_multipliers)
    ])


def state_spec(cfg: FalconH1Config) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
    """Per-layer arrays ONE slot holds: (name, shape, dtype). The recurrent
    state stays float32 whatever ``cfg.dtype`` is: it is rewritten every
    token, so a rounding compounds where a key is written once."""
    return (
        ("ssm", (cfg.mamba_n_heads, cfg.mamba_d_state, cfg.mamba_d_head), F32),
        ("conv", (cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype),
    )


def state_update(use_pallas: bool, interpret: bool = False) -> Callable:
    """The decode rows' recurrence: the Pallas launch or its twin."""
    if use_pallas:
        return partial(pallas_ssm.ssm_state_update, interpret=interpret)
    return pallas_ssm.ssm_state_update_reference


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer_params(rng: jax.Array, cfg: FalconH1Config) -> Params:
    k = jax.random.split(rng, 12)
    h, d, inter = cfg.hidden_size, cfg.mamba_d_ssm, cfg.intermediate_size
    H = cfg.mamba_n_heads
    dt = cfg.dtype

    def mat(key, fan_in, fan_out, mult):
        """std = 1 / (sqrt(fan_in) x the multipliers on input and output)."""
        return (jax.random.normal(key, (fan_in, fan_out))
                / (math.sqrt(fan_in) * mult)).astype(dt)

    a_in = cfg.attention_in_multiplier
    inproj = jnp.concatenate([
        jax.random.normal(kk, (h, w)) / (math.sqrt(h) * cfg.ssm_in_multiplier * m)
        for kk, w, m in zip(jax.random.split(k[0], 5), cfg.in_proj_segments,
                            cfg.ssm_multipliers)
    ], axis=1).astype(dt)
    # Mamba-2's convention: A in [1, 16], step sizes log-uniform in
    # [1e-3, 1e-1] (dt_bias their inverse softplus), D ones, the convolution
    # uniform in +-1/sqrt(kernel)
    dt0 = jnp.exp(jax.random.uniform(k[2], (H,)) * (math.log(1e-1) - math.log(1e-3))
                  + math.log(1e-3))
    bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
    p: Params = {
        "in_norm": jnp.ones((h,), dt),
        "ff_norm": jnp.ones((h,), dt),
        "w_inproj": inproj,
        "conv_w": jax.random.uniform(
            k[1], (cfg.mamba_d_conv, cfg.conv_dim), minval=-bound, maxval=bound
        ).astype(dt),
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(F32),
        "A_log": jnp.log(jax.random.uniform(k[3], (H,), minval=1.0, maxval=16.0)).astype(F32),
        "D": jnp.ones((H,), F32),
        "ssm_norm": jnp.ones((d,), dt),
        "w_outproj": mat(k[4], d, h, cfg.ssm_out_multiplier),
        "wq": mat(k[5], h, cfg.q_size, a_in),
        "wk": mat(k[6], h, cfg.kv_size, a_in * cfg.key_multiplier),
        "wv": mat(k[7], h, cfg.kv_size, a_in),
        "wo": mat(k[8], cfg.q_size, h, cfg.attention_out_multiplier),
        "w_gate": mat(k[9], h, inter, cfg.mlp_multipliers[0]),
        "w_up": mat(k[10], h, inter, 1.0),
        "w_down": mat(k[11], inter, h, cfg.mlp_multipliers[1]),
    }
    if cfg.mamba_conv_bias:
        p["conv_b"] = jax.random.uniform(
            jax.random.fold_in(k[1], 1), (cfg.conv_dim,), minval=-bound, maxval=bound
        ).astype(dt)
    return p


def init_params(rng: jax.Array, cfg: FalconH1Config) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    h = cfg.hidden_size
    params: Params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, h))
                  / cfg.embedding_multiplier).astype(cfg.dtype),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": [init_layer_params(keys[i + 2], cfg) for i in range(cfg.num_layers)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[1], (h, cfg.vocab_size))
            / (math.sqrt(h) * cfg.lm_head_multiplier)
        ).astype(cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# the mixer between its projections: convolution, recurrence
# ---------------------------------------------------------------------------


def _conv_bias(p: Params):
    return p["conv_b"].astype(F32) if "conv_b" in p else 0.0


def _split_xbc(cfg: FalconH1Config, xBC: jax.Array):
    """[..., conv_dim] -> x [..., H, P], B and C [..., G, N]."""
    d, bc = cfg.mamba_d_ssm, cfg.bc_size
    lead = xBC.shape[:-1]
    x = xBC[..., :d].reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head)
    B = xBC[..., d:d + bc].reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state)
    C = xBC[..., d + bc:].reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state)
    return x, B, C


def _step_sizes(p: Params, dt: jax.Array) -> jax.Array:
    return jax.nn.softplus(dt.astype(F32) + p["dt_bias"])


def mix_chunk(p: Params, cfg: FalconH1Config, xBC: jax.Array, dt: jax.Array,
              state: jax.Array, tail: jax.Array, n_real) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A run of tokens of ONE request, from ``state`` [H, N, P] and ``tail``
    [conv - 1, conv_dim] (its last inputs before the run): xBC [T, conv_dim]
    before the convolution, dt [T, H] raw. Tokens from ``n_real`` on are a
    bucket's padding: their step size is forced to 0, which makes the
    recurrence the identity, and the tail handed on is the last ``conv - 1``
    REAL inputs. Returns (y [T, H, P], state', tail')."""
    K = cfg.mamba_d_conv
    T = xBC.shape[0]
    with jax.named_scope("ssm_conv"):
        seq = jnp.concatenate([tail.astype(xBC.dtype), xBC], axis=0)  # [T + K - 1, C]
        w = p["conv_w"].astype(F32)
        acc = _conv_bias(p) + sum(
            seq[j:j + T].astype(F32) * w[j] for j in range(K)
        )
        conv = jax.nn.silu(acc).astype(xBC.dtype)
        new_tail = jax.lax.dynamic_slice_in_dim(seq, n_real, K - 1, axis=0)
    x, B, C = _split_xbc(cfg, conv)
    step = jnp.where((jnp.arange(T) < n_real)[:, None], _step_sizes(p, dt), 0.0)
    with jax.named_scope("ssm_scan"):
        y, new_state = pallas_ssm.ssm_scan(
            state, x, B, C, step, -jnp.exp(p["A_log"]), p["D"],
            chunk=min(cfg.mamba_chunk_size, T),
        )
    return y, new_state, new_tail.astype(tail.dtype)


def mix_rows(p: Params, cfg: FalconH1Config, xBC: jax.Array, dt: jax.Array,
             states: jax.Array, tails: jax.Array, live: jax.Array,
             update: Optional[Callable] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ONE token a row: xBC [R, conv_dim], dt [R, H], states [R, H, N, P],
    tails [R, conv - 1, conv_dim], live [R] bool. A row that is not live
    leaves its state and its tail as they were. ``update`` is the recurrence
    (``pallas_ssm.ssm_state_update`` or its twin, the default). Returns
    (y [R, H, P], states', tails')."""
    update = update or pallas_ssm.ssm_state_update_reference
    with jax.named_scope("ssm_conv"):
        seq = jnp.concatenate([tails.astype(xBC.dtype), xBC[:, None]], axis=1)  # [R, K, C]
        acc = _conv_bias(p) + jnp.sum(
            seq.astype(F32) * p["conv_w"].astype(F32)[None], axis=1
        )
        conv = jax.nn.silu(acc).astype(xBC.dtype)
        new_tails = jnp.where(live[:, None, None], seq[:, 1:].astype(tails.dtype), tails)
    x, B, C = _split_xbc(cfg, conv)
    with jax.named_scope("ssm_update"):
        new_states, y = update(
            states, x, B, C, _step_sizes(p, dt), -jnp.exp(p["A_log"]), p["D"], live
        )
    return y, new_states, new_tails


def stateless_mix(params: Params, cfg: FalconH1Config):
    """``mix`` for a whole sequence [T] from zeros that keeps nothing: the
    pooled forward of embeddings, and the tests' plain forward."""
    (_, s_shape, s_dt), (_, c_shape, c_dt) = state_spec(cfg)

    def mix(xBC, dt, layer_idx):
        y, _, _ = mix_chunk(
            params["layers"][layer_idx], cfg, xBC, dt,
            jnp.zeros(s_shape, s_dt), jnp.zeros(c_shape, c_dt), xBC.shape[0],
        )
        return y

    return mix


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

# mix(xBC [..., conv_dim], dt [..., H], layer_idx) -> y [..., H, P]
MixFn = Callable[[jax.Array, jax.Array, int], jax.Array]


def layer_forward(p: Params, cfg: FalconH1Config, x: jax.Array, cos, sin,
                  attend, mix: MixFn, layer_idx: int, mup: jax.Array) -> jax.Array:
    dt_ = x.dtype
    lead = x.shape[:-1]
    u = rms_norm(x, p["in_norm"], cfg.rms_norm_eps)
    # -- the state-space mixer -----------------------------------------------
    d = cfg.mamba_d_ssm
    with jax.named_scope("ssm_in_proj"):
        proj = (((u * cfg.ssm_in_multiplier).astype(dt_) @ p["w_inproj"]).astype(F32)
                * mup).astype(dt_)
    z, xBC, dt = proj[..., :d], proj[..., d:d + cfg.conv_dim], proj[..., d + cfg.conv_dim:]
    y = mix(xBC, dt, layer_idx).reshape(*lead, d)
    with jax.named_scope("ssm_gate_norm"):
        # gate first, then RMSNorm over each of the n_groups' lanes
        g = y.astype(F32) * jax.nn.silu(z.astype(F32))
        gg = g.reshape(*lead, cfg.mamba_n_groups, d // cfg.mamba_n_groups)
        gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        g = (gg.reshape(*lead, d) * p["ssm_norm"].astype(F32)).astype(dt_)
    with jax.named_scope("ssm_out_proj"):
        o_s = ((g @ p["w_outproj"]).astype(F32) * cfg.ssm_out_multiplier).astype(dt_)
    # -- attention, from the same normalised input ---------------------------
    ua = (u * cfg.attention_in_multiplier).astype(dt_)
    q = (ua @ p["wq"]).reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = ((ua @ p["wk"]).astype(F32) * cfg.key_multiplier).astype(dt_)
    k = k.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = (ua @ p["wv"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    a = attend(q, k, v, layer_idx).reshape(*lead, cfg.q_size)
    o_a = ((a @ p["wo"]).astype(F32) * cfg.attention_out_multiplier).astype(dt_)
    x = x + o_s + o_a
    # -- gated MLP ------------------------------------------------------------
    v_ = rms_norm(x, p["ff_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu((v_ @ p["w_gate"]).astype(F32) * cfg.mlp_multipliers[0]).astype(dt_)
    down = (gate * (v_ @ p["w_up"])) @ p["w_down"]
    return x + (down.astype(F32) * cfg.mlp_multipliers[1]).astype(dt_)


def forward(params: Params, cfg: FalconH1Config, token_ids: jax.Array,
            positions: jax.Array, attend, mix: Optional[MixFn] = None) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden]. Without ``mix``
    the sequence runs from zeros and keeps nothing (``stateless_mix``: a
    whole sequence [S] only)."""
    if mix is None:
        mix = stateless_mix(params, cfg)
    x = (params["embed"][token_ids].astype(F32) * cfg.embedding_multiplier).astype(cfg.dtype)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]
    mup = mup_vector(cfg)
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(layer, cfg, x, cos, sin, attend, mix, i, mup)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: FalconH1Config, hidden: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (hidden @ w).astype(F32) * cfg.lm_head_multiplier
