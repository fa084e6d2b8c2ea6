"""MiniCPM-SALA: a hybrid of LAYER KINDS (``model_type`` ``minicpm_sala``).
One layer in four (``mixer_types`` ``minicpm4``) is softmax grouped-query
attention that, past ``dense_len`` keys, attends over the BLOCKS OF KEYS EACH
QUERY CHOOSES, a kv head, from a cache of pooled keys (InfLLM-v2, MiniCPM4
arXiv 2506.07900: no learned indexer); the other three (``lightning-attn``)
are linear attention with a FIXED DECAY A HEAD (Lightning Attention, arXiv
2401.04658: no delta rule, no convolution). Every layer's feed-forward is
the dense SwiGLU; the embedding, every residual branch and the head carry
the muP scalings of the MiniCPM line.

    h0 = scale_emb * E[tok]
    h += (scale_depth / sqrt(mup_denominator)) * Mixer(RMSNorm(h))
    h += (scale_depth / sqrt(mup_denominator)) * SwiGLU(RMSNorm(h))
    logits = W_head (RMSNorm(h) / (hidden_size / dim_model_base))

``mup_denominator`` is the PUBLISHED depth whatever depth is held.

Two kinds of state, and a layer keeps ONE of them. A sparse layer keeps pages
of keys and values and one pooled key a page a kv head behind ``attend``
(``infllm=`` an ``ops/attention.InfLlmQuery`` with the selection's sizes:
ops/paged_attention.py has what the seam does with it); a lightning layer
keeps, for each request, a state ``[heads, d, d]`` in float32 behind the
second seam, ``mix(q, k, v, layer) -> y`` (the heads' normalised, rotated
queries and keys in; the heads' outputs before their norm and gate out).
``registry.page_layers`` / ``state_layers`` say which layers keep what. The
forward stays a pure function of ``(params, tokens, positions, attend,
mix)``.

What the public configuration does not fix is chosen here and listed, each
with its alternative, in ``benchmarks/configs/minicpm-sala-9b-d8.json``
(``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as att
from ..ops import pallas_lightning as plight
from .llama import Params, apply_rope, rms_norm, rope_cos_sin

F32 = jnp.float32
# the StepStats counters of this family's recurrence: lightning_rows_updated, ...
STATE_PREFIX = "lightning"
# the weight q_norm is drawn at in a sparse layer (init_layer_params says why)
SPARSE_Q_NORM = 3.0


@dataclasses.dataclass(frozen=True)
class MiniCpmSalaConfig:
    vocab_size: int = 512
    hidden_size: int = 256
    num_layers: int = 4                   # layers held
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    intermediate_size: int = 512
    rope_theta: float = 10000.0           # the lightning layers' rotary
    rms_norm_eps: float = 1e-6
    max_position: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # which of the layers held are block-sparse attention; the others are
    # lightning attention
    sparse_layers: Tuple[int, ...] = (0,)
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32             # the PUBLISHED depth
    dim_model_base: int = 256
    # lightning attention
    lightning_heads: int = 4
    lightning_head_dim: int = 64
    # the selection (sparse_config of the family; all assumed)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if any(not 0 <= i < self.num_layers for i in self.sparse_layers):
            raise ValueError("sparse_layers names layers that are held")
        if not self.sparse_layers or len(self.sparse_layers) == self.num_layers:
            raise ValueError("a hybrid holds layers of both kinds")
        self.selection  # the sizes fit each other

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def lightning_size(self) -> int:
        """Lanes of q (and of k, of v) over the lightning heads."""
        return self.lightning_heads * self.lightning_head_dim

    @property
    def branch_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @property
    def selection(self) -> att.InfLlmQuery:
        return att.InfLlmQuery(
            self.kernel_size, self.kernel_stride, self.block_size, self.topk,
            self.init_blocks, self.window_size, self.dense_len,
        )

    def is_sparse(self, layer_idx: int) -> bool:
        return layer_idx in self.sparse_layers

    @classmethod
    def tiny(cls, **kw) -> "MiniCpmSalaConfig":
        """Test scale that keeps the shape's oddities: two periods of 4, 16
        query heads a kv head and 2 kv heads (which choose differently), a
        selection small enough that contexts of a few hundred tokens cross
        ``dense_len`` and leave blocks unchosen (pages of 16: a pooled key is
        two pages, a block two pages, 2 + 1 + 3 of a context's blocks)."""
        base = dict(
            vocab_size=512, hidden_size=128, num_layers=8, num_heads=32,
            num_kv_heads=2, head_dim=16, intermediate_size=256,
            sparse_layers=(0, 4), lightning_heads=4, lightning_head_dim=16,
            block_size=32, topk=2, init_blocks=1, window_size=64,
            dense_len=128, max_position=2048,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def minicpm_sala_9b(cls, num_layers: int = 32,
                        sparse_layers: Optional[Tuple[int, ...]] = None,
                        **kw) -> "MiniCpmSalaConfig":
        """openbmb/MiniCPM-SALA's config.json (``sparse_layers``: where its
        ``mixer_types`` says ``minicpm4``)."""
        if sparse_layers is None:
            sparse_layers = tuple(
                i for i in (0, 9, 16, 17, 22, 29, 30, 31) if i < num_layers
            )
        return cls(
            vocab_size=73448, hidden_size=4096, num_layers=num_layers,
            num_heads=32, num_kv_heads=2, head_dim=128,
            intermediate_size=16384, max_position=524288,
            sparse_layers=sparse_layers, lightning_heads=32,
            lightning_head_dim=128, **kw,
        )


def state_spec(cfg: MiniCpmSalaConfig) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
    """Per-layer arrays ONE slot holds in a lightning layer: (name, shape,
    dtype). The matrix state stays float32 whatever ``cfg.dtype`` is: it is
    rewritten every token and its slowest head forgets in 256, so a rounding
    compounds where a key is written once."""
    d = cfg.lightning_head_dim
    return (("lightning", (cfg.lightning_heads, d, d), F32),)


def page_layers(cfg: MiniCpmSalaConfig) -> Tuple[int, ...]:
    """The layers that keep pages (and pooled keys): the sparse ones."""
    return tuple(sorted(cfg.sparse_layers))


def state_layers(cfg: MiniCpmSalaConfig) -> Tuple[int, ...]:
    """The layers that keep slot state: the lightning ones."""
    return tuple(i for i in range(cfg.num_layers) if not cfg.is_sparse(i))


def pooled_keys(cfg: MiniCpmSalaConfig) -> att.InfLlmQuery:
    """A page layer keeps one pooled key a page beside its pages: the sizes
    they are pooled and chosen by (``registry.pooled_keys``)."""
    return cfg.selection


def read_counters(cfg: MiniCpmSalaConfig) -> Tuple[str, ...]:
    """The ``StepStats`` fields ``forward`` adds to its ``stats``, in the
    order they ride a step's readback: what the step's real decode rows'
    launches were handed of what they could have read (keys, summed over
    rows and sparse layers), the (row, layer)s that selected, and the pooled keys every
    token of the step made final (a key a kv head)."""
    return ("infllm_keys_selected", "infllm_keys_causal", "infllm_rows_sparse",
            "infllm_pooled_keys_written")


def state_update(use_pallas: bool, interpret: bool = False) -> Callable:
    """The decode rows' recurrence: the Pallas launch or its twin."""
    if use_pallas:
        return partial(plight.lightning_state_update, interpret=interpret)
    return plight.lightning_state_update_reference


def decays(cfg: MiniCpmSalaConfig) -> jax.Array:
    """``lambda_h = exp(-2^(-8 (h + 1) / H))`` [H]: Lightning Attention's
    slopes, the same in every layer."""
    H = cfg.lightning_heads
    return jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(H, dtype=F32) + 1.0) / H)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _mat(key, fan_in: int, fan_out: int, dt, gain: float = 1.0) -> jax.Array:
    return (jax.random.normal(key, (fan_in, fan_out)) * (gain / math.sqrt(fan_in))).astype(dt)


def init_layer_params(rng: jax.Array, cfg: MiniCpmSalaConfig, layer_idx: int) -> Params:
    """Every matrix at ``1 / sqrt(fan_in)``, the norms ones, but a sparse
    layer's ``q_norm``: drawn at ``SPARSE_Q_NORM``, so that a query's logits
    spread by 3 over its keys where unit norms would spread by 1 and make the
    softmax over ten thousand keys, and with it every block score, nearly
    flat (a trained model's attention is peaked; a selection among equals
    tests nothing)."""
    k = jax.random.split(rng, 10)
    h, dt = cfg.hidden_size, cfg.dtype
    p: Params = {
        "in_norm": jnp.ones((h,), dt), "ff_norm": jnp.ones((h,), dt),
        "w_gate": _mat(k[0], h, cfg.intermediate_size, dt),
        "w_up": _mat(k[1], h, cfg.intermediate_size, dt),
        "w_down": _mat(k[2], cfg.intermediate_size, h, dt),
    }
    if cfg.is_sparse(layer_idx):
        d = cfg.head_dim
        p.update(
            wq=_mat(k[3], h, cfg.q_size, dt), wk=_mat(k[4], h, cfg.kv_size, dt),
            wv=_mat(k[5], h, cfg.kv_size, dt), wo=_mat(k[6], cfg.q_size, h, dt),
            w_ogate=_mat(k[7], h, cfg.q_size, dt),
            q_norm=jnp.full((d,), SPARSE_Q_NORM, dt), k_norm=jnp.ones((d,), dt),
        )
    else:
        n, d = cfg.lightning_size, cfg.lightning_head_dim
        p.update(
            wq=_mat(k[3], h, n, dt), wk=_mat(k[4], h, n, dt),
            wv=_mat(k[5], h, n, dt), wo=_mat(k[6], n, h, dt),
            w_ogate=_mat(k[7], h, n, dt),
            q_norm=jnp.ones((d,), dt), k_norm=jnp.ones((d,), dt),
            o_norm=jnp.ones((d,), dt),
        )
    return p


def init_params(rng: jax.Array, cfg: MiniCpmSalaConfig) -> Params:
    """The embedding is drawn at ``1 / scale_emb`` and the head at
    ``hidden_size / dim_model_base`` times ``1 / sqrt(hidden)``, so that
    ``h0`` and the logits are of order 1 under the muP scalings (as a
    trained muP model's are) and every branch's share of the stream shows."""
    keys = jax.random.split(rng, cfg.num_layers + 2)
    h = cfg.hidden_size
    params: Params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, h)) / cfg.scale_emb).astype(cfg.dtype),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": [init_layer_params(keys[i + 2], cfg, i) for i in range(cfg.num_layers)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _mat(keys[1], h, cfg.vocab_size, cfg.dtype,
                                 gain=h / cfg.dim_model_base)
    return params


# ---------------------------------------------------------------------------
# lightning attention between its projections
# ---------------------------------------------------------------------------


def mix_chunk(p: Params, cfg: MiniCpmSalaConfig, q: jax.Array, k: jax.Array,
              v: jax.Array, state: jax.Array, n_real):
    """A run of tokens of ONE request, from ``state`` [H, d, d]: q, k, v [T,
    H, d] (normalised and rotated). Tokens from ``n_real`` on are a bucket's
    padding: no decay, a zero key. Returns (y [T, H, d] float32, state')."""
    del p
    with jax.named_scope("lightning_scan"):
        scale = cfg.lightning_head_dim ** -0.5
        return plight.lightning_scan(
            state, q.astype(F32) * scale, k, v, jnp.log(decays(cfg)), n_real
        )


def mix_rows(p: Params, cfg: MiniCpmSalaConfig, q: jax.Array, k: jax.Array,
             v: jax.Array, states: jax.Array, live: jax.Array,
             update: Optional[Callable] = None):
    """ONE token a row: q, k, v [R, H, d], states [R, H, d, d], live [R]
    bool. A row that is not live leaves its state as it was. ``update`` is
    the recurrence (``pallas_lightning.lightning_state_update`` or its twin,
    the default). Returns (y [R, H, d] float32, states')."""
    del p
    update = update or plight.lightning_state_update_reference
    with jax.named_scope("lightning_update"):
        scale = cfg.lightning_head_dim ** -0.5
        new_states, y = update(states, q.astype(F32) * scale, k, v, decays(cfg), live)
    return y, new_states


def stateless_mix(params: Params, cfg: MiniCpmSalaConfig):
    """``mix`` for a whole sequence [T] from zeros that keeps nothing: the
    pooled forward of embeddings, and the tests' plain forward."""
    (_, shape, dt), = state_spec(cfg)

    def mix(q, k, v, layer_idx):
        y, _ = mix_chunk(params["layers"][layer_idx], cfg, q, k, v,
                         jnp.zeros(shape, dt), q.shape[0])
        return y

    return mix


def stateless_attend(cfg: MiniCpmSalaConfig):
    """``attend`` over ONE whole sequence from nothing (``q`` [S, h, d]): the
    pure-JAX twin of the sparse layers' attention with no pages and no pool
    (ops/attention.infllm_attention)."""

    def attend(q, k, v, layer_idx, infllm=None):
        return att.infllm_attention(q, k, v, infllm)

    return attend


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

# mix(q [..., H, d], k, v, layer_idx) -> y [..., H, d]
MixFn = Callable[[jax.Array, jax.Array, jax.Array, int], jax.Array]


def _gate(a: jax.Array, u: jax.Array, w: jax.Array) -> jax.Array:
    """``a * sigmoid(W_g u)``, elementwise, in float32."""
    return (a.astype(F32) * jax.nn.sigmoid((u @ w).astype(F32))).astype(u.dtype)


def _sparse_layer(p: Params, cfg: MiniCpmSalaConfig, u: jax.Array, attend,
                  layer_idx: int) -> jax.Array:
    lead, eps = u.shape[:-1], cfg.rms_norm_eps
    q = (u @ p["wq"]).reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = (u @ p["wk"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = (u @ p["wv"]).reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    a = attend(q, k, v, layer_idx, infllm=cfg.selection)         # no positions
    with jax.named_scope("infllm_gate"):
        a = _gate(a.reshape(*lead, cfg.q_size), u, p["w_ogate"])
    return a @ p["wo"]


def _lightning_layer(p: Params, cfg: MiniCpmSalaConfig, u: jax.Array, cos, sin,
                     mix: MixFn, layer_idx: int) -> jax.Array:
    lead, eps = u.shape[:-1], cfg.rms_norm_eps
    heads = (*lead, cfg.lightning_heads, cfg.lightning_head_dim)
    with jax.named_scope("lightning_proj"):
        q = apply_rope(rms_norm((u @ p["wq"]).reshape(heads), p["q_norm"], eps), cos, sin)
        k = apply_rope(rms_norm((u @ p["wk"]).reshape(heads), p["k_norm"], eps), cos, sin)
        v = (u @ p["wv"]).reshape(heads)
    y = mix(q, k, v, layer_idx)                                  # [..., H, d] float32
    with jax.named_scope("lightning_gate_norm"):
        y = rms_norm(y.astype(F32), p["o_norm"].astype(F32), eps)
        o = _gate(y.reshape(*lead, cfg.lightning_size), u, p["w_ogate"])
    return o @ p["wo"]


def layer_forward(p: Params, cfg: MiniCpmSalaConfig, x: jax.Array, cos, sin,
                  attend, mix: MixFn, layer_idx: int) -> jax.Array:
    scale = jnp.asarray(cfg.branch_scale, x.dtype)
    u = rms_norm(x, p["in_norm"], cfg.rms_norm_eps)
    if cfg.is_sparse(layer_idx):
        x = x + scale * _sparse_layer(p, cfg, u, attend, layer_idx)
    else:
        x = x + scale * _lightning_layer(p, cfg, u, cos, sin, mix, layer_idx)
    t = rms_norm(x, p["ff_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu((t @ p["w_gate"]).astype(F32)).astype(x.dtype)
    return x + scale * ((gate * (t @ p["w_up"])) @ p["w_down"])


def _count_selection(cfg: MiniCpmSalaConfig, stats, positions: jax.Array,
                     handed: List[jax.Array]) -> None:
    """What the step's real decode rows read: ``handed``, the lengths of the
    views each sparse layer's decode launch was given ([R, kvh] a layer, the
    step's last R rows: ops/attention.infllm_decode_rows), a count a kv
    head; beside every causal key, and the pooled keys the step's tokens
    made final."""
    spec, L = cfg.selection, len(cfg.sparse_layers)
    rows, valid = stats.decode_rows.reshape(-1), stats.valid.reshape(-1)
    p = positions.reshape(-1)
    n = p + 1
    sparse = rows & (n > spec.dense_len)
    final = valid & (p % spec.stride == spec.stride - 1) & (p >= spec.kernel - 1)
    selected = sum(
        jnp.where(rows[-h.shape[0]:, None], h, 0).sum() for h in handed
    ) // cfg.num_kv_heads
    stats.add_reads(
        infllm_keys_selected=jnp.asarray(selected),
        infllm_keys_causal=jnp.where(rows, n, 0).sum() * L,
        infllm_rows_sparse=sparse.sum() * L,
        infllm_pooled_keys_written=final.sum() * L * cfg.num_kv_heads,
    )


def forward(params: Params, cfg: MiniCpmSalaConfig, token_ids: jax.Array,
            positions: jax.Array, attend, mix: Optional[MixFn] = None,
            stats=None, lora: Optional[Callable] = None) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden]. ``positions``
    rotate the lightning layers' queries and keys; the sparse layers have
    none. Without ``mix`` the sequence runs from zeros and keeps nothing
    (``stateless_mix``: a whole sequence [S] only). ``stats``
    (models/moe.RoutingStats, used for its readback alone): what the step's
    real decode rows were handed by the seam (``InfLlmQuery.handed``), under
    ``read_counters``' names."""
    if lora is not None:
        raise NotImplementedError("LoRA is not supported for the minicpm_sala family")
    if mix is None:
        mix = stateless_mix(params, cfg)
    handed: List[jax.Array] = []
    if stats is not None:
        served = attend

        def attend(q, k, v, layer_idx, infllm):
            return served(q, k, v, layer_idx,
                          infllm=dataclasses.replace(infllm, handed=handed))
    x = params["embed"][token_ids] * jnp.asarray(cfg.scale_emb, cfg.dtype)
    cos, sin = rope_cos_sin(positions, cfg.lightning_head_dim, cfg.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(layer, cfg, x, cos, sin, attend, mix, i)
    if stats is not None:
        _count_selection(cfg, stats, positions, handed)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: MiniCpmSalaConfig, hidden: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    hidden = hidden / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, hidden.dtype)
    return (hidden @ w).astype(F32)
