"""Multi-head Latent Attention (MLA) family: DeepSeek V2/V3/R1-style models
in functional JAX.

What the reference serves through engine adapters (recipes/deepseek-r1/,
trtllm/sglang workers), this framework owns as first-class model code, the
same way models/llama.py owns the dense family.

TPU-first design — the KV cache holds the COMPRESSED latent:

MLA projects hidden states down to a small shared latent ``c`` (kv_lora_rank
floats) plus one decoupled RoPE key ``k_pe`` (qk_rope_head_dim floats) per
token; per-head K/V are up-projections of ``c``. The serving win is the
"weight absorption" identity: folding the K up-projection into the query and
the V up-projection past the softmax turns attention into **MQA over the
latent**, so the cache per token is ``kv_lora_rank + qk_rope_head_dim``
floats instead of ``2 * heads * head_dim`` (DeepSeek V3: 576 vs 32768 — a
57x smaller cache, and decode on TPU is HBM-bandwidth-bound on exactly that
gather traffic):

    score_h(i) = q_nope_h . (W_uk_h c_i) + q_pe_h . k_pe_i
               = concat(W_uk_h^T q_nope_h, q_pe_h) . concat(c_i, k_pe_i)
    out_h      = W_uv_h (sum_i p_i c_i)

This maps onto the engine's existing attend contract with no engine changes:
``num_kv_heads = 1`` and ``head_dim = kv_lora_rank + qk_rope_head_dim``; the
cached "k" is ``concat(c, k_pe)``, the cached "v" is ``c`` zero-padded to
the same width, and the model applies ``W_uv`` to the attend output's first
``kv_lora_rank`` lanes. All paged/chunked/ring attention paths work
unchanged. Two subtleties:

- softmax scale: the engine's attention ops scale by 1/sqrt(q.shape[-1]);
  MLA wants 1/sqrt(qk_nope_head_dim + qk_rope_head_dim). The query is
  pre-multiplied by the ratio so the net scale is correct.
- TP: q heads (w_uq/w_uk/w_uv/wo) shard over the tp axis; the latent
  projections and the 1-head latent cache are replicated (an MQA cache
  cannot shard on heads — same layout real MLA deployments use).

Learned sparse attention (DSA, GLM-5.x / DeepSeek-V3.2; ``index_topk > 0``):
a small indexer scores every causal key for every query, the ``index_topk``
best are kept, and the softmax above runs over those alone. A layer whose
``indexer_types`` entry is ``full`` has an indexer and selects; a ``shared``
layer attends over the selection of the nearest ``full`` layer before it.
The cache of such a configuration is laid out for token-granular reads
(ops/attention.py): ``num_kv_heads`` rows of 128 lanes a token in either
paged array, the latent in the first, ``k_pe`` and the index key in the
second; the layer hands the seam an ``ops.attention.DsaQuery`` and gets
``[..., heads, kv_lora_rank]`` back.

A latent WITHOUT an indexer can take the same rows-of-128-lanes layout
(``rows_layout``; its widths must allow it, ``rows_capable``: ``kv_lora_rank``
a multiple of 256, ``qk_rope_head_dim <= 128``, DeepSeek-V3's and A.X-K1's
512 + 64), because a page of it is something a kernel can copy: the layer
hands the seam an ``ops.attention.LatentQuery`` (nothing selects: every causal
key is attended) and the seam answers with ``paged_latent_attention``
(ops/pallas_latent.py on the chip). The layout is chosen where the
parallelism is known: ``TpuEngine`` takes it on the one-chip text path
(``registry.place_latent``), and everywhere else (``tp`` / ``sp`` above 1, a
speculative draft, LoRA, an 8-bit cache, vision, a narrower latent) the
latent keeps the one-head contract described above and the pure-JAX path.

Rotary positions are plain ``rope_theta`` or, with ``rope_scaling_factor >
1``, YaRN as the DeepSeek lineage applies it: blended frequencies
(``llama.yarn_inv_freq``), cos and sin scaled by ``m(mscale) /
m(mscale_all_dim)`` and the softmax scale by ``m(mscale_all_dim) ** 2``,
``m(a) = 0.1 a ln(factor) + 1``.

FFN is the dense SwiGLU for ``num_experts == 0``, otherwise DeepSeek-MoE
style: ``first_dense_layers`` leading dense layers, sigmoid-or-softmax
top-k routing with ``routed_scaling_factor``, optional always-on shared
experts, reusing models/moe.py's expert paths (grouped / dense / EP-psum).
``mlp_layer_types`` (``dense`` / ``sparse`` a layer) overrides the leading-
dense rule; ``experts_held`` = (first, count) makes every sparse layer one
chip's share of a layer divided over chips: its stacks hold ``count``
experts, the router still chooses among all ``num_experts``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import LATENT_LANES, DsaQuery, LatentQuery
from . import moe as moelib
from .llama import (
    AttendFn,
    LlamaConfig,
    Params,
    apply_rope,
    rms_norm,
    rope_cos_sin,
    yarn_inv_freq,
)


@dataclasses.dataclass(frozen=True)
class MlaConfig(LlamaConfig):
    # attention (latent) dims
    q_lora_rank: int = 0            # 0 = full-rank q projection (V2-Lite)
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    # MoE FFN (num_experts == 0 -> dense SwiGLU everywhere)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    moe_scoring: str = "softmax"    # "sigmoid" = DeepSeek-V3 style
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 0
    first_dense_layers: int = 0
    # group-limited routing (V3 noaux_tc): experts partitioned into n_group
    # groups; selection first keeps the topk_group best groups (scored by
    # their top-2 expert sum), then top-k within the survivors
    n_group: int = 1
    topk_group: int = 1
    # checkpoint rope layout: True = interleaved pairs (HF rope_interleave,
    # the DeepSeek default) — the loader de-interleaves to rotate-half
    rope_interleave: bool = True
    # per-layer FFN kinds ("dense" / "sparse"); () = the leading-dense rule
    mlp_layer_types: Tuple[str, ...] = ()
    # (first, count): the experts this chip holds of every sparse layer
    experts_held: Optional[Tuple[int, int]] = None
    # learned sparse attention (index_topk == 0: none): the indexer's sizes
    # and, a layer, "full" (has an indexer, selects) or "shared" (attends
    # over the nearest full layer's selection)
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Tuple[str, ...] = ()
    # a latent WITHOUT an indexer held as rows of 128 lanes (with one it
    # always is). The engine sets it (registry.place_latent) where the
    # parallelism allows; False = one head of rank + rope lanes
    rows_layout: bool = False
    # YaRN (rope_scaling_factor <= 1: plain rotary positions)
    rope_scaling_factor: float = 1.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # what a family of LAYER KINDS states of ONE kind's attention
    # (models/dots3_note.py builds an MlaConfig a kind and calls this
    # module's layer with it); each at its default leaves this family's
    # programs as they were:
    # - the two normalised latents multiplied by a constant (the LongCat-
    #   Flash lineage's sqrt(hidden / rank)); the cache holds ``c`` after it
    q_latent_scale: float = 1.0
    kv_latent_scale: float = 1.0
    # - a headwise output gate, sigmoid(h W_g) a head a token, before W_o
    attention_gate: bool = False
    # - a rows-layout latent without an indexer that attends its last
    #   ``sliding_window`` keys alone (its own among them)
    sliding_window: Optional[int] = None
    # - rows a token of the SECOND paged array (0 = as many as the latent's:
    #   the engine allocates a layer's two arrays alike); 2 = the one tile a
    #   step reads, where the engine shapes a page group's arrays by layer
    #   kind (registry.page_shapes)
    aux_rows: int = 0
    # - the prefix of the attention's scopes in a device trace ("" = the
    #   seam's own, ``dsa_`` / ``latent_``)
    trace_scope: str = ""

    def __post_init__(self):
        # the engine reads num_kv_heads/head_dim as the KV-cache layout;
        # for MLA that layout IS the latent — pin it so presets can't drift
        if self.index_topk > 0:
            # rows of 128 lanes a token, so that a token can be read alone
            # (ops/attention.py has the layout)
            if (self.kv_lora_rank % (2 * LATENT_LANES)
                    or self.qk_rope_head_dim > LATENT_LANES
                    or self.index_head_dim > LATENT_LANES
                    or self.index_head_dim < self.qk_rope_head_dim
                    or self.q_lora_rank <= 0):
                raise ValueError(
                    "a latent cache read token by token needs kv_lora_rank a "
                    "multiple of 256, qk_rope_head_dim <= index_head_dim <= "
                    "128 and q_lora_rank > 0 (the indexer reads the query's "
                    "latent)"
                )
            kinds = self.indexer_types[: self.num_layers]
            if (len(kinds) != self.num_layers or kinds[0] != "full"
                    or set(kinds) - {"full", "shared"}):
                raise ValueError(
                    "indexer_types names every layer 'full' or 'shared', and "
                    "the first layer selects for itself"
                )
            object.__setattr__(
                self, "num_kv_heads", self.kv_lora_rank // LATENT_LANES
            )
            object.__setattr__(self, "head_dim", LATENT_LANES)
            return
        if self.rows_layout:
            if not self.rows_capable:
                raise ValueError(
                    "a latent held as rows needs kv_lora_rank a multiple of "
                    "256 and qk_rope_head_dim <= 128 (the cache holds it as "
                    "rows of 128 lanes, two to a tile)"
                )
            object.__setattr__(
                self, "num_kv_heads", self.kv_lora_rank // LATENT_LANES
            )
            object.__setattr__(self, "head_dim", LATENT_LANES)
            return
        object.__setattr__(self, "num_kv_heads", 1)
        object.__setattr__(
            self, "head_dim", self.kv_lora_rank + self.qk_rope_head_dim
        )

    @property
    def latent_rows(self) -> bool:
        """Whether the cache holds the latent as rows of 128 lanes a token
        (ops/attention.py has the layout): with an indexer always, without
        one where ``rows_layout`` says so."""
        return self.index_topk > 0 or self.rows_layout

    @property
    def rows_capable(self) -> bool:
        """Whether the widths allow the rows layout."""
        return (
            self.kv_lora_rank % (2 * LATENT_LANES) == 0
            and self.qk_rope_head_dim <= LATENT_LANES
        )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5``, times YaRN's ``m(mscale_all_dim) ** 2``
        where a scaling factor is stated."""
        return 1.0 / math.sqrt(self.qk_head_dim) * self.yarn_scale_factor

    @property
    def yarn_scale_factor(self) -> float:
        """``m(mscale_all_dim) ** 2``; exactly 1.0 without YaRN."""
        return _yarn_mscale(
            self.rope_scaling_factor, self.rope_mscale_all_dim
        ) ** 2

    @property
    def q_size(self) -> int:  # true q projection width (lora sizing etc.)
        return self.num_heads * self.qk_head_dim

    @classmethod
    def tiny_mla(cls, **kw) -> "MlaConfig":
        defaults = dict(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, intermediate_size=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny_mla_moe(cls, **kw) -> "MlaConfig":
        defaults = dict(
            vocab_size=512, hidden_size=128, num_layers=3, num_heads=4,
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, intermediate_size=256, q_lora_rank=96,
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=64,
            moe_scoring="sigmoid", routed_scaling_factor=2.0,
            num_shared_experts=1, first_dense_layers=1, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny_mla_dsa(cls, **kw) -> "MlaConfig":
        """Learned sparse attention at a test's size: a dense layer that
        selects, a sparse one that shares, a sparse one that selects; 8
        experts of which this share holds 4."""
        defaults = dict(
            vocab_size=512, hidden_size=128, num_layers=3, num_heads=4,
            kv_lora_rank=256, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, intermediate_size=256, q_lora_rank=96,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=64,
            moe_scoring="sigmoid", routed_scaling_factor=2.5,
            num_shared_experts=1, experts_held=(4, 4),
            mlp_layer_types=("dense", "sparse", "sparse"),
            index_topk=16, index_n_heads=4, index_head_dim=32,
            indexer_types=("full", "shared", "full"),
            tie_embeddings=False, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def deepseek_v2_lite(cls, vocab_size: int = 102400) -> "MlaConfig":
        """DeepSeek-V2-Lite (15.7B total / 2.4B active)."""
        return cls(
            vocab_size=vocab_size, hidden_size=2048, num_layers=27,
            num_heads=16, q_lora_rank=0, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            intermediate_size=10944, num_experts=64, num_experts_per_tok=6,
            moe_intermediate_size=1408, num_shared_experts=2,
            norm_topk_prob=False,  # V2-Lite uses unnormalized top-k weights
            first_dense_layers=1, rope_theta=10000.0, tie_embeddings=False,
        )

    @classmethod
    def deepseek_v3(cls, vocab_size: int = 129280) -> "MlaConfig":
        """DeepSeek-V3 / R1 (671B total / 37B active). Sharded over chips
        (``tp`` above 1: the only way it fits) the 512 + 64 latent is one
        576-lane head and attention runs the pure-JAX paged path; on the
        one-chip text path (a cut of it) the engine holds the latent as rows
        of 128 lanes and attention is the launch ``paged_latent_attention``."""
        return cls(
            vocab_size=vocab_size, hidden_size=7168, num_layers=61,
            num_heads=128, q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            intermediate_size=18432, num_experts=256, num_experts_per_tok=8,
            moe_intermediate_size=2048, moe_scoring="sigmoid",
            routed_scaling_factor=2.5, norm_topk_prob=True,
            num_shared_experts=1, first_dense_layers=3,
            n_group=8, topk_group=4,
            rope_theta=10000.0, tie_embeddings=False,
        )

    @classmethod
    def axk1(cls, vocab_size: int = 163840) -> "MlaConfig":
        """A.X-K1 (519B total): DeepSeek-V3's layer at 64 heads and 192
        experts, one leading dense layer, YaRN x32 from 4 096 positions."""
        return cls(
            vocab_size=vocab_size, hidden_size=7168, num_layers=61,
            num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            intermediate_size=18432, num_experts=192, num_experts_per_tok=8,
            moe_intermediate_size=2048, moe_scoring="sigmoid",
            routed_scaling_factor=2.5, norm_topk_prob=True,
            num_shared_experts=1, first_dense_layers=1,
            n_group=8, topk_group=4, rms_norm_eps=1e-6,
            rope_theta=10000.0, max_position=131072, tie_embeddings=False,
            rope_scaling_factor=32.0, rope_original_max_position=4096,
            rope_beta_fast=32.0, rope_beta_slow=1.0,
            rope_mscale=1.0, rope_mscale_all_dim=1.0,
        )


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def rope_tables(cfg: MlaConfig, positions: jax.Array):
    """cos/sin [..., qk_rope_head_dim / 2] (float32) of the rotary dims:
    plain ``rope_theta``, or YaRN where ``rope_scaling_factor > 1``."""
    rope = cfg.qk_rope_head_dim
    if cfg.rope_scaling_factor <= 1.0:
        return rope_cos_sin(positions, rope, cfg.rope_theta)
    inv_freq, _ = yarn_inv_freq(
        rope, cfg.rope_theta, cfg.rope_scaling_factor,
        cfg.rope_original_max_position, cfg.rope_beta_fast,
        cfg.rope_beta_slow,
    )
    # transformers' _compute_yarn_parameters: the ratio where the config
    # states both, else the recipe's own factor
    f = cfg.rope_scaling_factor
    if cfg.rope_mscale and cfg.rope_mscale_all_dim:
        att = _yarn_mscale(f, cfg.rope_mscale) / _yarn_mscale(
            f, cfg.rope_mscale_all_dim
        )
    else:
        att = _yarn_mscale(f, 1.0)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles) * att, jnp.sin(angles) * att


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _is_moe_layer(cfg: MlaConfig, layer_idx: int) -> bool:
    if cfg.mlp_layer_types:
        return cfg.mlp_layer_types[layer_idx] == "sparse"
    return cfg.num_experts > 0 and layer_idx >= cfg.first_dense_layers


def _selects(cfg: MlaConfig, layer_idx: int) -> bool:
    return cfg.index_topk > 0 and cfg.indexer_types[layer_idx] == "full"


def init_layer_params(rng: jax.Array, cfg: MlaConfig, layer_idx: int) -> Params:
    k = jax.random.split(rng, 16)
    # what an indexer or a held share adds draws from keys of its own, so
    # that the other parameters of a seed stay what they were
    kx = jax.random.split(jax.random.fold_in(rng, 1), 4)
    h = cfg.hidden_size
    nh, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(h)
    p: Params = {
        "attn_norm": jnp.ones((h,), cfg.dtype),
        "mlp_norm": jnp.ones((h,), cfg.dtype),
        # KV latent: one down-projection emitting [c (rank) | k_pe (rope)]
        "w_dkv": (jax.random.normal(k[0], (h, rank + rope)) * scale).astype(cfg.dtype),
        "kv_norm": jnp.ones((rank,), cfg.dtype),
        # per-head up-projections, head-stacked so TP shards the head dim
        # a matrix that reads a latent is drawn for the latent's energy: a
        # rescaled latent (``kv_latent_scale`` s, RMS s) at 1 / (s sqrt(rank))
        # = 1 / sqrt(hidden), the lineage's own convention for the rescale
        "w_uk": (
            jax.random.normal(k[1], (nh, nope, rank))
            / (math.sqrt(rank) * cfg.kv_latent_scale)
        ).astype(cfg.dtype),
        "w_uv": (
            jax.random.normal(k[2], (nh, rank, vd))
            / (math.sqrt(rank) * cfg.kv_latent_scale)
        ).astype(cfg.dtype),
        "wo": (jax.random.normal(k[3], (nh * vd, h)) * scale).astype(cfg.dtype),
    }
    if cfg.attention_gate:
        p["w_g"] = (
            jax.random.normal(jax.random.fold_in(rng, 2), (h, nh)) * scale
        ).astype(cfg.dtype)
    if cfg.q_lora_rank > 0:
        p["w_dq"] = (
            jax.random.normal(k[4], (h, cfg.q_lora_rank)) * scale
        ).astype(cfg.dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), cfg.dtype)
        p["w_uq"] = (
            jax.random.normal(k[5], (cfg.q_lora_rank, nh * (nope + rope)))
            / (math.sqrt(cfg.q_lora_rank) * cfg.q_latent_scale)
        ).astype(cfg.dtype)
    else:
        p["wq"] = (
            jax.random.normal(k[5], (h, nh * (nope + rope))) * scale
        ).astype(cfg.dtype)
    if _selects(cfg, layer_idx):
        # the indexer: n small query heads off the query's latent, ONE key a
        # token (LayerNorm with bias) and a weight a head off the hidden state
        nI, dI = cfg.index_n_heads, cfg.index_head_dim
        p["w_iq"] = (
            jax.random.normal(kx[0], (cfg.q_lora_rank, nI * dI))
            / (math.sqrt(cfg.q_lora_rank) * cfg.q_latent_scale)
        ).astype(cfg.dtype)
        p["w_ik"] = (jax.random.normal(kx[1], (h, dI)) * scale).astype(cfg.dtype)
        p["ik_norm_w"] = jnp.ones((dI,), cfg.dtype)
        p["ik_norm_b"] = jnp.zeros((dI,), cfg.dtype)
        p["w_iw"] = (jax.random.normal(kx[2], (h, nI)) * scale).astype(cfg.dtype)
    if _is_moe_layer(cfg, layer_idx):
        E, inter = cfg.num_experts, cfg.moe_intermediate_size
        iscale = 1.0 / math.sqrt(inter)
        p["w_router"] = (jax.random.normal(k[6], (h, E)) * scale).astype(cfg.dtype)
        if cfg.moe_scoring == "sigmoid":
            # aux-free load-balancing bias (updated out-of-band in training;
            # inference just reads it — HF e_score_correction_bias). Zero,
            # except where a share is held: there the weights are a seed's,
            # and a bias drawn small makes selection differ from the weights
            p["router_bias"] = (
                jnp.zeros((E,), jnp.float32) if cfg.experts_held is None
                else 0.1 * jax.random.normal(kx[3], (E,), jnp.float32)
            )
        if cfg.experts_held is not None:
            E = cfg.experts_held[1]   # the stacks hold this chip's experts
        # expert stacks use their own names (w_e*) so the TP partition spec
        # can shard the expert dim without colliding with the 2-D dense-layer
        # w_gate/w_up/w_down sharing the per-layer spec table
        p["w_egate"] = (jax.random.normal(k[7], (E, h, inter)) * scale).astype(cfg.dtype)
        p["w_eup"] = (jax.random.normal(k[8], (E, h, inter)) * scale).astype(cfg.dtype)
        p["w_edown"] = (jax.random.normal(k[9], (E, inter, h)) * iscale).astype(cfg.dtype)
        if cfg.num_shared_experts > 0:
            si = inter * cfg.num_shared_experts
            p["w_shared_gate"] = (
                jax.random.normal(k[10], (h, si)) * scale
            ).astype(cfg.dtype)
            p["w_shared_up"] = (
                jax.random.normal(k[11], (h, si)) * scale
            ).astype(cfg.dtype)
            p["w_shared_down"] = (
                jax.random.normal(k[12], (si, h)) / math.sqrt(si)
            ).astype(cfg.dtype)
    else:
        inter = cfg.intermediate_size
        iscale = 1.0 / math.sqrt(inter)
        p["w_gate"] = (jax.random.normal(k[7], (h, inter)) * scale).astype(cfg.dtype)
        p["w_up"] = (jax.random.normal(k[8], (h, inter)) * scale).astype(cfg.dtype)
        p["w_down"] = (jax.random.normal(k[9], (inter, h)) * iscale).astype(cfg.dtype)
    return p


def init_params(rng: jax.Array, cfg: MlaConfig) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    params: Params = {
        "embed": (
            jax.random.normal(keys[0], (cfg.vocab_size, cfg.hidden_size)) * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.dtype),
        "layers": [
            init_layer_params(keys[i + 2], cfg, i) for i in range(cfg.num_layers)
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[1], (cfg.hidden_size, cfg.vocab_size)) * 0.02
        ).astype(cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# routing (DeepSeek flavors) + FFN
# ---------------------------------------------------------------------------


# ONE definition for every family whose expert layer is this one (models/
# moe.py: sigmoid or softmax scores, a selection bias, optional groups,
# normalised top-k, a held share, the shared expert): solar_open2 calls the
# same three
route = moelib.route_scored
expert_params = moelib.expert_stacks
EXPERT_STACKS = moelib.ROUTED_SHARED_STACKS
_moe_ffn = moelib.routed_shared_ffn


def _dense_ffn(p: Params, cfg: MlaConfig, x: jax.Array) -> jax.Array:
    gate = jax.nn.silu((x @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return (gate * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rope_front(x: jax.Array, cos: jax.Array, sin: jax.Array, rope: int):
    """Rotate the first ``rope`` dims of ``x [..., heads, d]``."""
    return jnp.concatenate(
        [apply_rope(x[..., :rope], cos, sin), x[..., rope:]], axis=-1
    )


def _scaled(x: jax.Array, scale: float) -> jax.Array:
    """``x * scale`` rounded once (a normalised latent's constant rescale);
    ``x`` itself at 1.0."""
    if scale == 1.0:
        return x
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _lanes(x: jax.Array) -> jax.Array:
    """Zero-pad the last dim to a row of ``LATENT_LANES`` lanes."""
    pad = LATENT_LANES - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _rows_attention(p, cfg, h, cq, c, k_pe, q_abs, q_pe, cos, sin,
                    attend, layer_idx, carry):
    """Latent attention over the rows layout: over the positions an indexer
    selects (this layer's, or the one ``carry`` brings from the nearest
    selecting layer: a ``DsaQuery`` for the seam) or, without an indexer,
    over every causal key (a ``LatentQuery``). The layer's rows in the
    layout. Returns [..., heads, kv_lora_rank]."""
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nI, dI = cfg.index_n_heads, cfg.index_head_dim
    lead = h.shape[:-1]
    kI = jnp.zeros((*lead, LATENT_LANES), cfg.dtype)
    named = {"scope": cfg.trace_scope} if cfg.trace_scope else {}
    if cfg.index_topk == 0:
        ask = {"latent": LatentQuery(
            scale=cfg.softmax_scale, window=cfg.sliding_window, **named
        )}
    else:
        dsa = DsaQuery(scale=cfg.softmax_scale, topk=cfg.index_topk, **named)
        ask = {"dsa": dsa}
    if _selects(cfg, layer_idx):
        qI = (cq @ p["w_iq"]).reshape(*lead, nI, dI)
        dsa.index_q = _rope_front(qI, cos, sin, rope).astype(cfg.dtype)
        dsa.index_w = (h @ p["w_iw"]).astype(jnp.float32) * (
            nI ** -0.5 * dI ** -0.5
        )
        kf = (h @ p["w_ik"]).astype(jnp.float32)
        kf = kf - kf.mean(-1, keepdims=True)
        kf = kf * jax.lax.rsqrt((kf * kf).mean(-1, keepdims=True) + 1e-6)
        kf = kf * p["ik_norm_w"].astype(jnp.float32) + p["ik_norm_b"].astype(
            jnp.float32
        )
        kI = _lanes(
            _rope_front(kf.astype(cfg.dtype)[..., None, :], cos, sin, rope)[..., 0, :]
        )
    elif cfg.index_topk > 0:
        dsa.selected = carry["selected"]
    k_rows = c.reshape(*lead, cfg.num_kv_heads, LATENT_LANES)
    aux = jnp.stack([_lanes(k_pe[..., 0, :]), kI.astype(k_pe.dtype)], axis=-2)
    aux = jnp.pad(
        aux,
        [(0, 0)] * len(lead)
        + [(0, (cfg.aux_rows or cfg.num_kv_heads) - 2), (0, 0)],
    )
    q_lat = jnp.concatenate([q_abs, _lanes(q_pe)], axis=-1)
    o = attend(
        q_lat.astype(cfg.dtype), k_rows.astype(cfg.dtype),
        aux.astype(cfg.dtype), layer_idx, **ask,
    )
    if cfg.index_topk > 0:
        carry["selected"] = dsa.selected
        if dsa.index_chunk_reads is not None:
            # the same tables in every layer: one selecting layer's count
            # is each's
            carry["index_chunk_reads"] = dsa.index_chunk_reads
    else:
        # the same tables in every layer: the last layer's count is each's
        carry["chunk_reads"] = ask["latent"].chunk_reads
    return o


def layer_forward(
    p: Params,
    cfg: MlaConfig,
    x: jax.Array,                 # [..., S, hidden]
    cos: jax.Array,               # [..., S, 1, rope/2]
    sin: jax.Array,
    attend: AttendFn,
    layer_idx: int,
    expert_fn=None,
    stats=None,
    matmul=moelib.grouped_matmul_reference,
    carry: Optional[dict] = None,
) -> jax.Array:
    nh, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lead = x.shape[:-1]           # [..., S]

    h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    # -- queries
    cq = None
    if cfg.q_lora_rank > 0:
        cq = _scaled(
            rms_norm(h @ p["w_dq"], p["q_norm"], cfg.rms_norm_eps),
            cfg.q_latent_scale,
        )
        q = cq @ p["w_uq"]
    else:
        q = h @ p["wq"]
    q = q.reshape(*lead, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, cos, sin)
    # -- latent KV
    ckv = h @ p["w_dkv"]                                   # [..., rank+rope]
    c = _scaled(
        rms_norm(ckv[..., :rank], p["kv_norm"], cfg.rms_norm_eps),
        cfg.kv_latent_scale,
    )
    k_pe = apply_rope(ckv[..., None, rank:], cos, sin)     # [..., 1, rope]
    # -- absorb W_uk into q: MQA over the latent
    q_abs = jnp.einsum("...hn,hnr->...hr", q_nope, p["w_uk"])
    if cfg.latent_rows:
        o = _rows_attention(
            p, cfg, h, cq, c, k_pe, q_abs, q_pe, cos, sin, attend,
            layer_idx, carry,
        )
    else:
        q_prime = jnp.concatenate([q_abs, q_pe], axis=-1)  # [..., nh, rank+rope]
        # attend ops scale by 1/sqrt(rank+rope); MLA wants 1/sqrt(nope+rope)
        # (times YaRN's factor, exactly 1.0 without it)
        q_prime = q_prime * (
            math.sqrt((rank + rope) / (nope + rope)) * cfg.yarn_scale_factor
        )
        k_prime = jnp.concatenate([c[..., None, :], k_pe], axis=-1)
        cl = c[..., None, :]                               # [..., 1, rank]
        v_prime = jnp.pad(
            cl, [(0, 0)] * (cl.ndim - 1) + [(0, rope)]
        )
        o = attend(
            q_prime.astype(cfg.dtype), k_prime.astype(cfg.dtype),
            v_prime.astype(cfg.dtype), layer_idx,
        )                                                  # [..., nh, rank+rope]
    # -- un-absorb W_uv past the softmax
    attn = jnp.einsum("...hr,hrv->...hv", o[..., :rank], p["w_uv"])
    if cfg.attention_gate:
        # one scalar a head a token, on the un-absorbed values: it rides the
        # product above (or the one below), no pass of its own
        with jax.named_scope(f"{cfg.trace_scope or 'mla'}_gate"):
            g = jax.nn.sigmoid((h @ p["w_g"]).astype(jnp.float32))
            attn = (attn * g[..., None]).astype(attn.dtype)
    x = x + attn.reshape(*lead, nh * cfg.v_head_dim) @ p["wo"]
    # -- FFN
    h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    if _is_moe_layer(cfg, layer_idx):
        # routing indexes per token: flatten leading dims to [T, H]
        flat = h.reshape(-1, h.shape[-1])
        y = _moe_ffn(
            p, cfg, flat, expert_fn=expert_fn, stats=stats, matmul=matmul
        )
        return x + y.reshape(h.shape)
    return x + _dense_ffn(p, cfg, h)


def read_counters(cfg: MlaConfig) -> Tuple[str, ...]:
    """The ``StepStats`` fields ``forward`` adds to its ``stats`` behind the
    routing's three (``RoutingStats.add_reads``), in the order they ride the
    step's readback: what the real decode rows read of a latent held as rows."""
    if not cfg.latent_rows:
        return ()
    if cfg.index_topk > 0:
        return ("dsa_keys_causal", "dsa_keys_scored", "dsa_keys_selected",
                "dsa_index_chunks_whole", "dsa_index_chunks_run")
    return ("mla_keys_attended", "mla_decode_rows", "mla_chunks_whole",
            "mla_chunks_run")


def forward(
    params: Params,
    cfg: MlaConfig,
    token_ids: jax.Array,        # [S] int32
    positions: jax.Array,        # [S] int32
    attend: AttendFn,
    lora: Optional[Callable] = None,
    inputs_embeds: Optional[jax.Array] = None,
    expert_fn=None,
    stats=None,
    matmul=moelib.grouped_matmul_reference,
) -> jax.Array:
    """``stats`` (moe.RoutingStats): the one-chip grouped expert path counts
    its routing into it, a configuration with an indexer the keys its real
    decode rows saw, scored and attended and the whole chunks of pages its
    indexers' keys were read by, of which as runs, and a rows-layout latent without
    one the keys its real decode rows attended over, those rows, and the
    whole chunks of pages its rows read, of which as runs."""
    if lora is not None:
        raise NotImplementedError("LoRA is not supported for the MLA family")
    x = params["embed"][token_ids] if inputs_embeds is None else inputs_embeds
    cos, sin = rope_tables(cfg, positions)
    cos, sin = cos[..., None, :], sin[..., None, :]
    carry: dict = {}
    for i, layer in enumerate(params["layers"]):
        x = layer_forward(
            layer, cfg, x, cos, sin, attend, i, expert_fn=expert_fn,
            stats=stats, matmul=matmul, carry=carry,
        )
    if stats is not None and cfg.latent_rows:
        # what the real decode rows read of the latent, summed over rows and
        # layers, under the names of StepStats' fields (read_counters)
        rows = stats.decode_rows.reshape(-1)
        seen = jnp.where(rows, positions.reshape(-1) + 1, 0)
        if cfg.index_topk > 0:
            # the keys a row could see, those an indexer scored (selecting
            # layers only) and those attended over; the whole chunks of
            # pages the selecting layers' read of the index keys took (the
            # step's tables to their last page, a mixed step's chunk row
            # too) and those read as runs
            n_sel = sum(_selects(cfg, i) for i in range(cfg.num_layers))
            whole, run = carry["index_chunk_reads"]
            stats.add_reads(
                dsa_keys_causal=seen.sum() * cfg.num_layers,
                dsa_keys_scored=seen.sum() * n_sel,
                dsa_keys_selected=(
                    jnp.minimum(seen, cfg.index_topk).sum() * cfg.num_layers
                ),
                dsa_index_chunks_whole=whole * n_sel,
                dsa_index_chunks_run=run * n_sel,
            )
        else:
            # the keys attended over (each row its whole context), the rows;
            # the whole chunks of pages under the contexts of the step's
            # latent rows (the chunk's row too) and those read as runs
            whole, run = carry["chunk_reads"]
            stats.add_reads(
                mla_keys_attended=seen.sum() * cfg.num_layers,
                mla_decode_rows=rows.sum() * cfg.num_layers,
                mla_chunks_whole=whole * cfg.num_layers,
                mla_chunks_run=run * cfg.num_layers,
            )
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: MlaConfig, hidden: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return (hidden @ params["embed"].T).astype(jnp.float32)
    return (hidden @ params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# reference (uncompressed) attention — test oracle
# ---------------------------------------------------------------------------


def reference_attention(
    p: Params, cfg: MlaConfig, h_normed: jax.Array, positions: jax.Array
) -> jax.Array:
    """Causal MLA attention with K/V fully materialized per head (no
    absorption, no latent cache) — the semantics the absorbed/MQA serving
    path must reproduce. Returns the post-``wo`` projection delta [S, H]."""
    nh, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    S = h_normed.shape[0]
    cos, sin = rope_tables(cfg, positions)
    cos, sin = cos[..., None, :], sin[..., None, :]
    if cfg.q_lora_rank > 0:
        q = rms_norm(h_normed @ p["w_dq"], p["q_norm"], cfg.rms_norm_eps) @ p["w_uq"]
    else:
        q = h_normed @ p["wq"]
    q = q.reshape(S, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, cos, sin)
    ckv = h_normed @ p["w_dkv"]
    c = rms_norm(ckv[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
    k_pe = apply_rope(ckv[..., None, rank:], cos, sin)[:, 0]   # [S, rope]
    # materialize per-head K (nope part) and V from the latent
    k_nope = jnp.einsum("sr,hnr->shn", c, p["w_uk"])           # [S, nh, nope]
    v = jnp.einsum("sr,hrv->shv", c, p["w_uv"])                # [S, nh, vd]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (S, nh, rope))], axis=-1
    )
    qf = jnp.concatenate([q_nope, q_pe], axis=-1).astype(jnp.float32)
    s = jnp.einsum("shd,thd->hst", qf, k.astype(jnp.float32))
    s = s / math.sqrt(nope + rope) * cfg.yarn_scale_factor
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hst,thv->shv", pattn, v.astype(jnp.float32))
    return (
        o.astype(cfg.dtype).reshape(S, nh * cfg.v_head_dim) @ p["wo"]
    )
