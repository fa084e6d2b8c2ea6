"""Solar Open 2: a hybrid of LAYER KINDS (``model_type`` ``solar_open2``).
Three layers in four mix tokens with Kimi Delta Attention (KDA, arXiv
2510.26692: the gated delta rule with a decay a channel, a matrix state a
head), the fourth (``gqa_layers``) with softmax grouped-query attention that
has NO positions and an output gate; every layer's feed-forward is the
routed one of the DeepSeek-V3 / glm4_moe lineage (sigmoid scores, a
selection bias, normalised top-k, one shared expert), which is not written
here: ``models/moe.py`` ``routed_shared_ffn`` is the one definition.

Two kinds of state, and a layer keeps ONE of them. A GQA layer keeps pages
of keys and values behind ``attend``; a KDA layer keeps, for each request, a
state ``[heads, d_k, d_v]`` in float32 and the last ``conv - 1`` inputs of
its causal convolution behind the second seam, ``mix(qkv, f, b, layer) ->
y`` (the channels before their convolution, the decay's and beta's raw
projections in; the heads' outputs before the gated norm out).
``registry.page_layers`` / ``state_layers`` say which layers keep what, and
the engine allocates accordingly. The forward stays a pure function of
``(params, tokens, positions, attend, mix)``; who owns the state decides what
``mix`` is (``mix_chunk`` over a run of tokens from a given state,
``mix_rows`` for one token a row, ``stateless_mix`` a whole sequence from
zeros).

What the public configuration does not fix is chosen here and listed, each
with its alternative, in ``benchmarks/configs/solar-open2-ep16-d8.json``
(``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import pallas_kda
from . import moe as moelib
from .llama import Params, rms_norm

F32 = jnp.float32
# the StepStats counters of this family's recurrence: kda_rows_updated, ...
STATE_PREFIX = "kda"
EXPERT_STACKS = moelib.ROUTED_SHARED_STACKS
# std of the drawn selection bias (init_layer_params says why this small)
ROUTER_BIAS_STD = 0.01


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 512                 # as HELD (a slice of the published)
    hidden_size: int = 256
    num_layers: int = 4                   # layers held
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    intermediate_size: int = 512          # unused: no layer is dense
    rope_theta: float = 10000.0           # unused: use_rope is false
    rms_norm_eps: float = 1e-5
    max_position: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # which of the layers held are softmax attention; the others are KDA
    gqa_layers: Tuple[int, ...] = (0,)
    use_gqa_gate: bool = True
    # KDA
    kda_num_heads: int = 4
    kda_head_dim: int = 64                # d_k = d_v
    kda_conv_kernel: int = 4
    kda_low_rank: int = 64                # W_f's and W_g's inner width
    kda_allow_neg_eigval: bool = True     # beta in (0, 2)
    # the routed feed-forward (the names models/moe.py routed_shared_ffn reads)
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    norm_topk_prob: bool = True
    moe_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the experts this chip holds of every layer
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if any(not 0 <= i < self.num_layers for i in self.gqa_layers):
            raise ValueError("gqa_layers names layers that are held")
        if not self.gqa_layers or len(self.gqa_layers) == self.num_layers:
            raise ValueError("a hybrid holds layers of both kinds")

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def kda_size(self) -> int:
        """Lanes of q (and of k, of v) over the KDA heads."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: q | k | v."""
        return 3 * self.kda_size

    def is_gqa(self, layer_idx: int) -> bool:
        return layer_idx in self.gqa_layers

    @classmethod
    def tiny(cls, **kw) -> "SolarOpen2Config":
        """Test scale that keeps the shape's oddities: a period of 4 that
        starts on a GQA layer (two periods), 4 query heads a kv head, more KDA
        heads than attention's, a held share of the experts, beta in (0, 2),
        channels that forget fast and slow (``init_layer_params``)."""
        base = dict(
            vocab_size=512, hidden_size=128, num_layers=8, num_heads=8,
            num_kv_heads=2, head_dim=16, gqa_layers=(0, 4),
            kda_num_heads=4, kda_head_dim=16, kda_low_rank=16,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
            experts_held=(4, 4),
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def solar_open2_250b(cls, num_layers: int = 48, vocab_size: int = 196608,
                         experts_held: Optional[Tuple[int, int]] = None) -> "SolarOpen2Config":
        """upstage/Solar-Open2-250B's config.json."""
        return cls(
            vocab_size=vocab_size, hidden_size=4096, num_layers=num_layers,
            num_heads=64, num_kv_heads=8, head_dim=128, intermediate_size=10240,
            max_position=1048576,
            gqa_layers=tuple(range(0, num_layers, 4)),
            kda_num_heads=64, kda_head_dim=128, kda_low_rank=128,
            num_experts=320, num_experts_per_tok=8, moe_intermediate_size=1280,
            experts_held=experts_held,
        )


def state_spec(cfg: SolarOpen2Config) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
    """Per-layer arrays ONE slot holds in a KDA layer: (name, shape, dtype).
    The matrix state stays float32 whatever ``cfg.dtype`` is: it is rewritten
    every token, so a rounding compounds where a key is written once."""
    d = cfg.kda_head_dim
    return (
        ("kda", (cfg.kda_num_heads, d, d), F32),
        ("conv", (cfg.kda_conv_kernel - 1, cfg.conv_dim), cfg.dtype),
    )


def page_layers(cfg: SolarOpen2Config) -> Tuple[int, ...]:
    """The layers that keep pages: the GQA ones."""
    return tuple(sorted(cfg.gqa_layers))


def state_layers(cfg: SolarOpen2Config) -> Tuple[int, ...]:
    """The layers that keep slot state: the KDA ones."""
    return tuple(i for i in range(cfg.num_layers) if not cfg.is_gqa(i))


def state_update(use_pallas: bool, interpret: bool = False) -> Callable:
    """The decode rows' recurrence: the Pallas launch or its twin."""
    if use_pallas:
        return partial(pallas_kda.kda_state_update, interpret=interpret)
    return pallas_kda.kda_state_update_reference


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _mat(key, fan_in: int, fan_out: int, dt) -> jax.Array:
    return (jax.random.normal(key, (fan_in, fan_out)) / math.sqrt(fan_in)).astype(dt)


def init_layer_params(rng: jax.Array, cfg: SolarOpen2Config, layer_idx: int) -> Params:
    k = jax.random.split(rng, 24)
    h, dt = cfg.hidden_size, cfg.dtype
    p: Params = {"in_norm": jnp.ones((h,), dt), "ff_norm": jnp.ones((h,), dt)}
    if cfg.is_gqa(layer_idx):
        p.update(
            wq=_mat(k[0], h, cfg.q_size, dt), wk=_mat(k[1], h, cfg.kv_size, dt),
            wv=_mat(k[2], h, cfg.kv_size, dt), wo=_mat(k[3], cfg.q_size, h, dt),
        )
        if cfg.use_gqa_gate:
            p["w_gate"] = _mat(k[4], h, cfg.q_size, dt)
    else:
        H, d, r, K = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_low_rank, cfg.kda_conv_kernel
        n = cfg.kda_size
        # Mamba-2's convention, as fla's KimiDeltaAttention: rates A in
        # [1, 16] a head, step sizes log-uniform in [1e-3, 1e-1] a CHANNEL
        # (dt_bias their inverse softplus): a channel at rate 16 and step 0.1
        # forgets in under a token, one at 1 and 1e-3 in a thousand
        dt0 = jnp.exp(jax.random.uniform(k[5], (n,)) * (math.log(1e-1) - math.log(1e-3))
                      + math.log(1e-3))
        bound = 1.0 / math.sqrt(K)
        p.update(
            w_qkv=_mat(k[6], h, 3 * n, dt),                      # columns q | k | v
            conv_w=jax.random.uniform(k[7], (K, 3 * n), minval=-bound, maxval=bound).astype(dt),
            w_f1=_mat(k[8], h, r, dt), w_f2=_mat(k[9], r, n, dt),
            dt_bias=(dt0 + jnp.log(-jnp.expm1(-dt0))).astype(F32),
            A_log=jnp.log(jax.random.uniform(k[10], (H,), minval=1.0, maxval=16.0)).astype(F32),
            w_b=_mat(k[11], h, H, dt),
            w_g1=_mat(k[12], h, r, dt), w_g2=_mat(k[13], r, n, dt),
            b_g=(0.5 * jax.random.normal(k[14], (n,))).astype(dt),
            out_norm=jnp.ones((d,), dt),
            wo=_mat(k[15], n, h, dt),
        )
    # the routed feed-forward, under the names routed_shared_ffn reads
    E, inter = cfg.num_experts, cfg.moe_intermediate_size
    p["w_router"] = _mat(k[16], h, E, dt)
    # the selection bias is trained out of band, to BALANCE the experts'
    # load: drawn with std 0.01, which still moves two tokens in three to
    # another top-k than the unbiased scores choose (so a selection that
    # ignores it is wrong) and leaves the load near even (the busiest expert
    # 1.7 x the mean over 320). At 0.1 it decides the selection: the top of a
    # sigmoid is flat, one expert takes 10 x the mean and half of a held
    # share goes untouched a step (PERF.md section 6, PR 41)
    p["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(k[17], (E,), F32)
    held = E if cfg.experts_held is None else cfg.experts_held[1]
    scale, iscale = 1.0 / math.sqrt(h), 1.0 / math.sqrt(inter)
    p["w_egate"] = (jax.random.normal(k[18], (held, h, inter)) * scale).astype(dt)
    p["w_eup"] = (jax.random.normal(k[19], (held, h, inter)) * scale).astype(dt)
    p["w_edown"] = (jax.random.normal(k[20], (held, inter, h)) * iscale).astype(dt)
    si = inter * cfg.num_shared_experts
    p["w_shared_gate"] = _mat(k[21], h, si, dt)
    p["w_shared_up"] = _mat(k[22], h, si, dt)
    p["w_shared_down"] = _mat(k[23], si, h, dt)
    return p


def init_params(rng: jax.Array, cfg: SolarOpen2Config) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    h = cfg.hidden_size
    params: Params = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, h)).astype(cfg.dtype),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": [init_layer_params(keys[i + 2], cfg, i) for i in range(cfg.num_layers)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _mat(keys[1], h, cfg.vocab_size, cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# KDA between its projections: convolution, gates, recurrence
# ---------------------------------------------------------------------------


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _heads(cfg: SolarOpen2Config, conv: jax.Array):
    """[..., 3 n] after the convolution -> q, k float32 normalised (q scaled
    by d_k^-0.5) and v, each [..., H, d]."""
    lead, n = conv.shape[:-1], cfg.kda_size
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    q, k, v = (conv[..., i * n:(i + 1) * n].reshape(*lead, H, d) for i in range(3))
    return _l2norm(q.astype(F32)) * d ** -0.5, _l2norm(k.astype(F32)), v


def _gates(p: Params, cfg: SolarOpen2Config, f: jax.Array, b: jax.Array):
    """The log-decay a channel g [..., H, d] <= 0 and beta [..., H]."""
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    step = jax.nn.softplus(f.astype(F32) + p["dt_bias"]).reshape(*f.shape[:-1], H, d)
    g = -jnp.exp(p["A_log"])[:, None] * step
    beta = jax.nn.sigmoid(b.astype(F32))
    return g, 2.0 * beta if cfg.kda_allow_neg_eigval else beta


def mix_chunk(p: Params, cfg: SolarOpen2Config, qkv: jax.Array, f: jax.Array,
              b: jax.Array, state: jax.Array, tail: jax.Array, n_real):
    """A run of tokens of ONE request, from ``state`` [H, d, d] and ``tail``
    [conv - 1, 3 n] (its last inputs before the run): qkv [T, 3 n] before
    the convolution, f [T, n] and b [T, H] raw. Tokens from ``n_real`` on are
    a bucket's padding: their decay is forced to 1 and their beta to 0, which
    makes the recurrence the identity, and the tail handed on is the last
    ``conv - 1`` REAL inputs. Returns (y [T, H, d] float32, state', tail')."""
    K, T = cfg.kda_conv_kernel, qkv.shape[0]
    with jax.named_scope("kda_conv"):
        seq = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=0)   # [T + K - 1, 3 n]
        w = p["conv_w"].astype(F32)
        conv = jax.nn.silu(sum(seq[j:j + T].astype(F32) * w[j] for j in range(K))).astype(qkv.dtype)
        new_tail = jax.lax.dynamic_slice_in_dim(seq, n_real, K - 1, axis=0)
    with jax.named_scope("kda_gates"):
        q, k, v = _heads(cfg, conv)
        g, beta = _gates(p, cfg, f, b)
        real = jnp.arange(T) < n_real
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
    with jax.named_scope("kda_scan"):
        y, new_state = pallas_kda.kda_scan(state, q, k, v, g, beta)
    return y, new_state, new_tail.astype(tail.dtype)


def mix_rows(p: Params, cfg: SolarOpen2Config, qkv: jax.Array, f: jax.Array,
             b: jax.Array, states: jax.Array, tails: jax.Array, live: jax.Array,
             update: Optional[Callable] = None):
    """ONE token a row: qkv [R, 3 n], f [R, n], b [R, H], states [R, H, d, d],
    tails [R, conv - 1, 3 n], live [R] bool. A row that is not live leaves
    its state and its tail as they were. ``update`` is the recurrence
    (``pallas_kda.kda_state_update`` or its twin, the default). Returns
    (y [R, H, d] float32, states', tails')."""
    update = update or pallas_kda.kda_state_update_reference
    with jax.named_scope("kda_conv"):
        seq = jnp.concatenate([tails.astype(qkv.dtype), qkv[:, None]], axis=1)  # [R, K, 3 n]
        conv = jax.nn.silu(
            jnp.sum(seq.astype(F32) * p["conv_w"].astype(F32)[None], axis=1)
        ).astype(qkv.dtype)
        new_tails = jnp.where(live[:, None, None], seq[:, 1:].astype(tails.dtype), tails)
    with jax.named_scope("kda_gates"):
        q, k, v = _heads(cfg, conv)
        g, beta = _gates(p, cfg, f, b)
    with jax.named_scope("kda_update"):
        new_states, y = update(states, q, k, v, jnp.exp(g), beta, live)
    return y, new_states, new_tails


def stateless_mix(params: Params, cfg: SolarOpen2Config):
    """``mix`` for a whole sequence [T] from zeros that keeps nothing: the
    pooled forward of embeddings, and the tests' plain forward."""
    (_, s_shape, s_dt), (_, c_shape, c_dt) = state_spec(cfg)

    def mix(qkv, f, b, layer_idx):
        y, _, _ = mix_chunk(
            params["layers"][layer_idx], cfg, qkv, f, b,
            jnp.zeros(s_shape, s_dt), jnp.zeros(c_shape, c_dt), qkv.shape[0],
        )
        return y

    return mix


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

# mix(qkv [..., 3 n], f [..., n], b [..., H], layer_idx) -> y [..., H, d]
MixFn = Callable[[jax.Array, jax.Array, jax.Array, int], jax.Array]


def _kda_layer(p: Params, cfg: SolarOpen2Config, u: jax.Array, mix: MixFn,
               layer_idx: int) -> jax.Array:
    dt_, lead = u.dtype, u.shape[:-1]
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    with jax.named_scope("kda_proj"):
        qkv = u @ p["w_qkv"]
        f = (u @ p["w_f1"]) @ p["w_f2"]
        b = u @ p["w_b"]
        gate = (u @ p["w_g1"]) @ p["w_g2"] + p["b_g"]
    y = mix(qkv, f, b, layer_idx)                              # [..., H, d] float32
    with jax.named_scope("kda_gate_norm"):
        y = y.astype(F32)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = y * p["out_norm"].astype(F32)
        o = (y * jax.nn.sigmoid(gate.astype(F32).reshape(*lead, H, d))).astype(dt_)
    return o.reshape(*lead, H * d) @ p["wo"]


def _gqa_layer(p: Params, cfg: SolarOpen2Config, u: jax.Array, attend,
               layer_idx: int) -> jax.Array:
    lead = u.shape[:-1]
    q = (u @ p["wq"]).reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = (u @ p["wk"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = (u @ p["wv"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    a = attend(q, k, v, layer_idx).reshape(*lead, cfg.q_size)  # no positions
    if cfg.use_gqa_gate:
        with jax.named_scope("gqa_gate"):
            a = (a.astype(F32) * jax.nn.sigmoid((u @ p["w_gate"]).astype(F32))).astype(u.dtype)
    return a @ p["wo"]


def layer_forward(p: Params, cfg: SolarOpen2Config, x: jax.Array, attend,
                  mix: MixFn, layer_idx: int, stats=None,
                  matmul=moelib.grouped_matmul_reference) -> jax.Array:
    u = rms_norm(x, p["in_norm"], cfg.rms_norm_eps)
    if cfg.is_gqa(layer_idx):
        x = x + _gqa_layer(p, cfg, u, attend, layer_idx)
    else:
        x = x + _kda_layer(p, cfg, u, mix, layer_idx)
    v = rms_norm(x, p["ff_norm"], cfg.rms_norm_eps)
    # routing indexes per token: flatten leading dims to [T, H]
    y = moelib.routed_shared_ffn(
        p, cfg, v.reshape(-1, v.shape[-1]), stats=stats, matmul=matmul
    )
    return x + y.reshape(v.shape)


def forward(params: Params, cfg: SolarOpen2Config, token_ids: jax.Array,
            positions: jax.Array, attend, mix: Optional[MixFn] = None,
            stats=None, matmul=moelib.grouped_matmul_reference,
            lora: Optional[Callable] = None) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden]. ``positions`` is
    every family's argument and unused here (no layer has positions).
    Without ``mix`` the sequence runs from zeros and keeps nothing
    (``stateless_mix``: a whole sequence [S] only). ``stats``
    (moe.RoutingStats): the grouped expert path counts its routing into it."""
    del positions
    if lora is not None:
        raise NotImplementedError("LoRA is not supported for the solar_open2 family")
    if mix is None:
        mix = stateless_mix(params, cfg)
    x = params["embed"][token_ids]
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(layer, cfg, x, attend, mix, i, stats=stats, matmul=matmul)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: SolarOpen2Config, hidden: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (hidden @ w).astype(F32)
