"""dots3-note-prev (``model_type`` ``dots3_note``): latent attention of TWO
widths in one model. A layer's kind (``layer_types``) fixes its attention
sizes:

- ``full_attention``: 128 heads of 128 + 64 over a 512-lane latent, attended
  over the ``index_topk`` keys a learned indexer of its own selects (the
  DeepSeek-V3.2 / GLM-5.2 mechanism; EVERY full layer has an indexer and
  selects for itself, the config names no ``indexer_types``);
- ``sliding_attention``: 64 heads of 192 + 64 over a 1024-lane latent OF ITS
  OWN, attended over the last ``sliding_window`` (513) keys, at a rotary base
  of its own.

Both kinds multiply their two normalised latents by ``sqrt(hidden / rank)``
(``apply_mla_qkv_lora_rescale``) and gate each head's output by
``sigmoid(h W_g)`` before ``W_o`` (``attention_gate_type`` ``headwise``). The
feed-forward is DeepSeek-V3's: one leading dense SwiGLU, then sigmoid-routed
experts chosen by ``s + b`` beside one shared expert.

Nothing of a layer is written here: a kind is an ``MlaConfig``
(``Dots3NoteConfig.kind``) and a layer is ``models/mla.py``'s
``layer_forward`` under it: its projections, absorption, rows layout,
indexer and expert paths, with the four hooks a kind states
(``q_latent_scale`` / ``kv_latent_scale``, ``attention_gate``,
``sliding_window``, ``aux_rows``). What this module owns is what a family of
kinds adds: the rotary tables a kind, the page groups and their token shapes,
the counters of both kinds.

Pages are kept BY LAYER KIND (``page_groups``, as models/cohere2_moe.py) and,
new here, SHAPED by it (``page_shapes``): the full layers' group holds 4 rows
of 128 lanes of latent a token for as long as the request, the sliding
layers' group 8 rows for one window; the second array of either is the one
``(2, 128)`` tile a step reads (``k_pe`` and, in a full layer, the index key).

What the public configuration does not fix is chosen here and listed, each
with its alternative, in ``benchmarks/configs/dots3-note-ep8-d5.json``
(``assumed``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import LATENT_LANES
from . import mla
from . import moe as moelib
from .llama import Params, rms_norm

EXPERT_STACKS = moelib.ROUTED_SHARED_STACKS
FULL, SLIDING = "full_attention", "sliding_attention"
# the one tile of the second paged array a step reads: [k_pe | index key]
AUX_ROWS = 2


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 512                 # as HELD (a slice of the published)
    hidden_size: int = 128
    num_layers: int = 9                   # layers held
    # a layer's kind, the public config.json's spelling, a layer HELD
    layer_types: Tuple[str, ...] = (FULL,) + (FULL, SLIDING, SLIDING, SLIDING) * 2
    intermediate_size: int = 256          # the leading dense layers' SwiGLU
    first_dense_layers: int = 1
    # full layers: the latent, its heads, its indexer
    num_heads: int = 4
    q_lora_rank: int = 96
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    rope_theta: float = 80000000.0
    index_topk: int = 24
    index_n_heads: int = 4
    index_head_dim: int = 32
    # sliding layers: a latent of their own
    swa_num_heads: int = 2
    swa_q_lora_rank: int = 96
    swa_kv_lora_rank: int = 512
    swa_qk_nope_head_dim: int = 48
    swa_qk_rope_head_dim: int = 16
    swa_v_head_dim: int = 32
    swa_rope_theta: float = 50000.0
    sliding_window: int = 9               # keys a query reads, its own among them
    # both kinds (and the headwise gate, which the family always runs)
    lora_rescale: bool = True             # apply_mla_qkv_lora_rescale
    # the routed feed-forward (the names models/moe.py routed_shared_ffn reads)
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    norm_topk_prob: bool = True
    moe_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 1
    # (first, count): the experts this chip holds of every sparse layer
    experts_held: Optional[Tuple[int, int]] = (4, 4)
    rms_norm_eps: float = 1e-5
    max_position: int = 524288
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = self.layer_types
        if len(kinds) != self.num_layers or set(kinds) - {FULL, SLIDING}:
            raise ValueError(
                "layer_types names every layer that is held, "
                f"{FULL!r} or {SLIDING!r}"
            )
        if kinds[0] != FULL and FULL in kinds:
            raise ValueError(
                "where both kinds are held the first layer is a full one: "
                "its page group lives as long as the request and is the "
                "engine's own"
            )
        if (self.index_topk <= 0 or self.q_lora_rank <= 0
                or self.swa_q_lora_rank <= 0):
            raise ValueError(
                "a full layer selects (index_topk > 0) and both kinds project "
                "their queries from a latent (q_lora_rank > 0)"
            )
        for i in range(self.num_layers):
            self.kind(i)        # MlaConfig judges a kind's widths

    # what the engine reads of every family as the pages' token shape: the
    # first layer's (its group is the engine's own: the full layers', where
    # any is held); every layer's own is ``page_shapes``'
    @property
    def num_kv_heads(self) -> int:
        return self.kind(0).num_kv_heads

    @property
    def head_dim(self) -> int:
        return LATENT_LANES

    @property
    def latent_rows(self) -> bool:
        """Both kinds hold their latent as rows of 128 lanes
        (registry.check_dsa_supported)."""
        return True

    @functools.cached_property
    def _kinds(self) -> dict:
        L = self.num_layers
        shared = dict(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=L, intermediate_size=self.intermediate_size,
            rms_norm_eps=self.rms_norm_eps, max_position=self.max_position,
            tie_embeddings=self.tie_embeddings, dtype=self.dtype,
            num_experts=self.num_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            moe_intermediate_size=self.moe_intermediate_size,
            norm_topk_prob=self.norm_topk_prob, moe_scoring=self.moe_scoring,
            routed_scaling_factor=self.routed_scaling_factor,
            num_shared_experts=self.num_shared_experts,
            experts_held=self.experts_held,
            mlp_layer_types=tuple(
                "dense" if i < self.first_dense_layers else "sparse"
                for i in range(L)
            ),
            attention_gate=True, aux_rows=AUX_ROWS,
            trace_scope="dots3",
        )

        def rescale(rank: int) -> float:
            return math.sqrt(self.hidden_size / rank) if self.lora_rescale else 1.0

        return {
            FULL: mla.MlaConfig(
                **shared, num_heads=self.num_heads,
                q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
                index_topk=self.index_topk, index_n_heads=self.index_n_heads,
                index_head_dim=self.index_head_dim,
                # no IndexShare: every layer of this kind selects for itself
                indexer_types=("full",) * L,
                q_latent_scale=rescale(self.q_lora_rank),
                kv_latent_scale=rescale(self.kv_lora_rank),
            ),
            SLIDING: mla.MlaConfig(
                **shared, num_heads=self.swa_num_heads,
                q_lora_rank=self.swa_q_lora_rank,
                kv_lora_rank=self.swa_kv_lora_rank,
                qk_nope_head_dim=self.swa_qk_nope_head_dim,
                qk_rope_head_dim=self.swa_qk_rope_head_dim,
                v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
                rows_layout=True, sliding_window=self.sliding_window,
                q_latent_scale=rescale(self.swa_q_lora_rank),
                kv_latent_scale=rescale(self.swa_kv_lora_rank),
            ),
        }

    def kind(self, layer_idx: int) -> mla.MlaConfig:
        """The layer's attention and feed-forward as ``models/mla.py`` runs
        them: its kind's sizes, every layer's place in the model."""
        return self._kinds[self.layer_types[layer_idx]]

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @classmethod
    def tiny(cls, **kw) -> "Dots3NoteConfig":
        """Test scale that keeps the shape: the dense layer and two periods
        (full, sliding x 3), two kinds with latents of 256 and 512 lanes, a
        window (9) shorter than ``index_topk`` (24) shorter than the tests'
        contexts, 8 experts top 2 of which this share holds 4."""
        return cls(**kw)

    @classmethod
    def dots3_note(cls, num_layers: int = 46, vocab_size: int = 152064,
                   experts_held: Optional[Tuple[int, int]] = None,
                   ) -> "Dots3NoteConfig":
        """dots-studio/dots3-note-prev's config.json (the language model):
        the first ``num_layers`` published layers."""
        period = (FULL, SLIDING, SLIDING, SLIDING)
        kinds = (FULL,) + tuple(period[i % 4] for i in range(45))
        return cls(
            vocab_size=vocab_size, hidden_size=5120, num_layers=num_layers,
            layer_types=kinds[:num_layers], intermediate_size=13824,
            first_dense_layers=1,
            num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=80000000.0, index_topk=2048, index_n_heads=64,
            index_head_dim=128,
            swa_num_heads=64, swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
            swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64,
            swa_v_head_dim=128, swa_rope_theta=50000.0, sliding_window=513,
            num_experts=256, num_experts_per_tok=8, moe_intermediate_size=1536,
            num_shared_experts=1, routed_scaling_factor=1.0,
            experts_held=experts_held,
        )


def page_groups(cfg: Dots3NoteConfig) -> Tuple[Tuple[Tuple[int, ...], Optional[int]], ...]:
    """(layers, lifetime) a group of page layers: the full layers' pages live
    as long as their request (``None``), the sliding layers' one window of
    positions. A configuration of one kind answers one group, whose pages
    live as long as the request (the engine's own: a lone sliding kind keeps
    its context's pages, and its window is the launch's alone)."""
    groups = ((cfg.layers_of(FULL), None),
              (cfg.layers_of(SLIDING), cfg.sliding_window))
    groups = tuple(g for g in groups if g[0])
    return groups if len(groups) > 1 else ((groups[0][0], None),)


def page_shapes(cfg: Dots3NoteConfig) -> tuple:
    """((k rows, lanes), (v rows, lanes)) a layer (registry.page_shapes): the
    kind's latent in the first array, the one tile a step reads (``k_pe``
    and, in a full layer, the index key) in the second."""
    return tuple(
        ((cfg.kind(i).num_kv_heads, LATENT_LANES), (AUX_ROWS, LATENT_LANES))
        for i in range(cfg.num_layers)
    )


def read_counters(cfg: Dots3NoteConfig) -> Tuple[str, ...]:
    """The ``StepStats`` fields ``forward`` adds to its ``stats`` behind the
    routing's three, in the order they ride a step's readback: the full
    layers' as ``mla.read_counters`` names them for an indexer (counted over
    the full layers, each of which selects), then the sliding layers': the
    keys the step's real decode rows read inside their windows, those rows,
    and the real tokens of a mixed step's chunk, each summed over the sliding
    layers."""
    return ("dsa_keys_causal", "dsa_keys_scored", "dsa_keys_selected",
            "dsa_index_chunks_whole", "dsa_index_chunks_run",
            "winlat_keys_read", "winlat_rows", "winlat_chunk_tokens")


# ---------------------------------------------------------------------------
# init + forward: models/mla.py's, a kind's config a layer
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: Dots3NoteConfig) -> Params:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    return {
        "embed": (
            jax.random.normal(keys[0], (cfg.vocab_size, cfg.hidden_size)) * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.dtype),
        "layers": [
            mla.init_layer_params(keys[i + 2], cfg.kind(i), i)
            for i in range(cfg.num_layers)
        ],
        "lm_head": (
            jax.random.normal(keys[1], (cfg.hidden_size, cfg.vocab_size)) * 0.02
        ).astype(cfg.dtype),
    }


def forward(params: Params, cfg: Dots3NoteConfig, token_ids: jax.Array,
            positions: jax.Array, attend, stats=None,
            matmul=moelib.grouped_matmul_reference,
            lora: Optional[Callable] = None) -> jax.Array:
    """Full stack -> final hidden states [..., S, hidden]. ``stats``
    (moe.RoutingStats): the grouped expert path counts its routing into it,
    and this adds what the real decode rows read of both kinds' latents
    (``read_counters``)."""
    if lora is not None:
        raise NotImplementedError("LoRA is not supported for the dots3_note family")
    x = params["embed"][token_ids]
    # rotary tables a kind: the base differs (and the rotary width may)
    ropes = {}
    for name, kcfg in cfg._kinds.items():
        cos, sin = mla.rope_tables(kcfg, positions)
        ropes[name] = (cos[..., None, :], sin[..., None, :])
    carry: dict = {}
    for i, layer in enumerate(params["layers"]):
        x = mla.layer_forward(
            layer, cfg.kind(i), x, *ropes[cfg.layer_types[i]], attend, i,
            stats=stats, matmul=matmul, carry=carry,
        )
    if stats is not None:
        n_full, n_win = len(cfg.layers_of(FULL)), len(cfg.layers_of(SLIDING))
        rows = stats.decode_rows.reshape(-1)
        seen = jnp.where(rows, positions.reshape(-1) + 1, 0)
        whole, run = carry.get("index_chunk_reads", (0, 0))   # no full layer
        chunk = stats.valid.reshape(-1) & ~rows
        stats.add_reads(
            dsa_keys_causal=seen.sum() * n_full,
            dsa_keys_scored=seen.sum() * n_full,
            dsa_keys_selected=jnp.minimum(seen, cfg.index_topk).sum() * n_full,
            dsa_index_chunks_whole=whole * n_full,
            dsa_index_chunks_run=run * n_full,
            winlat_keys_read=jnp.minimum(seen, cfg.sliding_window).sum() * n_win,
            winlat_rows=rows.sum() * n_win,
            winlat_chunk_tokens=chunk.sum() * n_win,
        )
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_logits(params: Params, cfg: Dots3NoteConfig, hidden: jax.Array) -> jax.Array:
    return (hidden @ params["lm_head"]).astype(jnp.float32)
