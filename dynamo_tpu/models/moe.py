"""Sparse MoE model family (Qwen3-MoE / Mixtral style) with TPU-native
expert parallelism.

The reference only passes EP knobs through to engine-internal all-to-all
(moe_expert_parallel_size etc., components/src/dynamo/trtllm/engine.py:
120-122; SGLang EPLB docs) — this framework owns the model, so EP is
implemented directly over the mesh:

* ``moe_ffn``            — dense reference (the in-repo oracle).
* ``moe_ffn_grouped``    — the one-chip serving path for every token count:
  the T*K assignments sorted by expert, one grouped multiplication for gate
  and up and one for down (ops/pallas_moe.py), weighted combine. Reads each
  touched expert once and multiplies only routed rows.
* ``moe_ffn_ep_psum``    — experts sharded over an axis, tokens REPLICATED
  on it (the engine's decode layout: EP rides the tp axis); each shard
  computes its local experts' contribution, one psum combines. Same
  collective cost as a TP row-parallel matmul.
* ``moe_ffn_ep_a2a``     — tokens SHARDED over the ep axis (GShard/Switch
  style): capacity-bounded dispatch, all-to-all to the expert owners over
  ICI, expert compute, all-to-all back, weighted combine. This is the
  scale path for large-batch prefill.

Routing is softmax-then-top-k with optional top-k renormalization
(Qwen3-MoE convention). A config may also state per-layer attention kinds
(``layer_types``: sliding-window layers beside full ones, each kind with its
own rotary table — plain for sliding, YaRN for full), the window riding the
paged attention ops' ``window`` argument as in gptoss.py and gemma.py.
Expert-load counts are returned for an EPLB-style rebalancing feed
(reference: docs/backends/sglang/expert-distribution-eplb.md — pattern
only).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas_moe import grouped_matmul_reference
from . import llama
from .llama import (
    AttendFn,
    Params,
    apply_rope,
    rms_norm,
    window_for_kind,
    yarn_inv_freq,
)


@dataclasses.dataclass(frozen=True)
class MoeConfig(llama.LlamaConfig):
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 128
    norm_topk_prob: bool = True
    # a2a dispatch capacity per (source shard, expert) = ceil(T*K/E * factor)
    capacity_factor: float = 2.0
    # EPLB (expert parallelism load balancing; reference: SGLang EPLB,
    # docs/backends/sglang/expert-distribution-eplb.md — redundant experts
    # rebalanced from observed load). R extra PHYSICAL expert slots hold
    # replicas of hot experts: the expert stacks are [E+R, ...] (static, so
    # zero recompiles), per-layer remap tables (eplb_slots/eplb_nrep, part
    # of the params pytree like LoRA tables) spread each logical expert's
    # tokens across its replicas, and TpuEngine.eplb_rebalance() re-plans
    # the replica set from measured counts at runtime. 0 disables.
    redundant_experts: int = 0
    # per-layer attention kind ("sliding_attention" / "full_attention", the
    # public config.json's spelling); empty = every layer full, as before
    layer_types: Tuple[str, ...] = ()
    sliding_window: Optional[int] = None
    # rotary parameters per layer kind: sliding layers use plain positions
    # at rope_theta; FULL layers use YaRN when rope_scaling_factor > 1
    # (``rope_attention_factor`` on cos and sin; None = YaRN's own
    # 0.1 ln(factor) + 1)
    rope_scaling_factor: float = 0.0
    rope_original_max_position: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_truncate: bool = True
    rope_attention_factor: Optional[float] = None

    @property
    def num_physical_experts(self) -> int:
        return self.num_experts + self.redundant_experts

    def window_for_layer(self, layer_idx: int) -> Optional[int]:
        if not self.layer_types:
            return None
        return window_for_kind(self.layer_types[layer_idx], self.sliding_window)

    @classmethod
    def tiny_moe(cls, **kw) -> "MoeConfig":
        defaults = dict(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=128,
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=64,
            dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def qwen3_30b_a3b(cls, vocab_size: int = 151936) -> "MoeConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=2048, num_layers=48,
            num_heads=32, num_kv_heads=4, head_dim=128,
            intermediate_size=6144,  # unused (all layers sparse)
            num_experts=128, num_experts_per_tok=8,
            moe_intermediate_size=768, rope_theta=1000000.0, qk_norm=True,
            tie_embeddings=False,
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer_params(rng: jax.Array, cfg: MoeConfig) -> Params:
    k = jax.random.split(rng, 9)
    h, qd, kvd = cfg.hidden_size, cfg.q_size, cfg.kv_size
    E, inter = cfg.num_experts, cfg.moe_intermediate_size
    scale = 1.0 / math.sqrt(h)
    iscale = 1.0 / math.sqrt(inter)
    p: Params = {
        "attn_norm": jnp.ones((h,), cfg.dtype),
        "mlp_norm": jnp.ones((h,), cfg.dtype),
        "wq": (jax.random.normal(k[0], (h, qd)) * scale).astype(cfg.dtype),
        "wk": (jax.random.normal(k[1], (h, kvd)) * scale).astype(cfg.dtype),
        "wv": (jax.random.normal(k[2], (h, kvd)) * scale).astype(cfg.dtype),
        "wo": (jax.random.normal(k[3], (qd, h)) * scale).astype(cfg.dtype),
        "w_router": (jax.random.normal(k[4], (h, E)) * scale).astype(cfg.dtype),
        # expert-stacked FFN weights: [E, ...] so the expert dim shards
        "w_gate": (jax.random.normal(k[5], (E, h, inter)) * scale).astype(cfg.dtype),
        "w_up": (jax.random.normal(k[6], (E, h, inter)) * scale).astype(cfg.dtype),
        "w_down": (jax.random.normal(k[7], (E, inter, h)) * iscale).astype(cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), cfg.dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), cfg.dtype)
    if cfg.redundant_experts > 0:
        ensure_eplb_layer(p, cfg)
    return p


def init_params(rng: jax.Array, cfg: MoeConfig) -> Params:
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
# routing
# ---------------------------------------------------------------------------


def route(
    p: Params, cfg: MoeConfig, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """softmax-then-top-k router. x [T, H] -> (weights [T, K] f32, idx [T, K])."""
    logits = jnp.dot(x, p["w_router"], preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi


def expert_load(cfg: MoeConfig, topi: jax.Array) -> jax.Array:
    """Tokens-per-expert counts [E] — the EPLB rebalancing feed."""
    oh = jax.nn.one_hot(topi.reshape(-1), cfg.num_experts, dtype=jnp.int32)
    return oh.sum(0)


# ---------------------------------------------------------------------------
# EPLB: redundant physical experts + replica remap tables
# ---------------------------------------------------------------------------


def default_eplb_tables(cfg: MoeConfig):
    """Identity-ish plan: redundant slot E+i replicates logical expert
    i % E (round-robin, so R > E just stacks more replicas per expert)
    until a measured rebalance replaces it. Returns numpy
    (slots [E, R+1], nrep [E], src [R]) — slots padded by repeating the
    primary so any index mod nrep lands on a valid replica; src[i] is the
    logical expert slot E+i serves (the weight-expansion gather)."""
    import numpy as np

    E, R = cfg.num_experts, cfg.redundant_experts
    slots = np.tile(np.arange(E, dtype=np.int32)[:, None], (1, R + 1))
    nrep = np.ones(E, np.int32)
    src = np.arange(R, dtype=np.int32) % E
    for i in range(R):
        e = src[i]
        slots[e, nrep[e]] = E + i
        nrep[e] += 1
    return slots, nrep, src


def ensure_eplb_layer(p: Params, cfg: MoeConfig) -> Params:
    """Expand a layer's logical [E, ...] expert stacks to physical
    [E+R, ...] and seed the remap tables. Idempotent — checkpoint loaders
    produce logical stacks; init and engine admission call this."""
    R = cfg.redundant_experts
    if R <= 0 or "w_gate" not in p:
        return p
    if p["w_gate"].shape[0] == cfg.num_physical_experts:
        return p
    slots, nrep, src = default_eplb_tables(cfg)
    for k in ("w_gate", "w_up", "w_down"):
        # default replicas mirror experts src[i] = i % E (the tables above)
        p[k] = jnp.concatenate([p[k], p[k][src]], axis=0)
    p["eplb_slots"] = jnp.asarray(slots)
    p["eplb_nrep"] = jnp.asarray(nrep)
    return p


def eplb_remap(p: Params, topi: jax.Array) -> jax.Array:
    """Map logical expert ids [T, K] to physical slots, spreading each
    expert's tokens round-robin across its replicas (the token index is the
    salt — deterministic, batch-independent per position)."""
    if "eplb_slots" not in p:
        return topi
    T, K = topi.shape
    nrep = p["eplb_nrep"][topi]                          # [T, K]
    pick = (jnp.arange(T, dtype=jnp.int32)[:, None] + jnp.arange(K)) % nrep
    return jnp.take_along_axis(
        p["eplb_slots"][topi], pick[..., None], axis=-1
    )[..., 0]


def primary_experts(p: Params, cfg: MoeConfig) -> Params:
    """View of the layer with only the logical expert slots (the dense and
    gather paths index logically and must not touch replicas)."""
    if "eplb_slots" not in p:
        return p
    out = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = p[k][: cfg.num_experts]
    return out


def _expert_mlp(w_gate, w_up, w_down, x, out_dtype):
    """x [E, B, H] through per-expert SwiGLU -> [E, B, H]."""
    gate = jnp.einsum("ebh,ehi->ebi", x, w_gate)
    up = jnp.einsum("ebh,ehi->ebi", x, w_up)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(out_dtype) * up
    return jnp.einsum("ebi,eih->ebh", act, w_down)


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------


def moe_ffn(p: Params, cfg: MoeConfig, x: jax.Array) -> jax.Array:
    """Dense reference: every expert computed for every token, masked
    combine. Exact (no capacity drops); O(T*E) compute — fine for tests and
    single-chip small-E serving."""
    T, H = x.shape
    p = primary_experts(p, cfg)  # EPLB replicas are an EP-path concern
    topw, topi = route(p, cfg, x)                        # [T, K]
    out_all = _expert_mlp(
        p["w_gate"], p["w_up"], p["w_down"],
        jnp.broadcast_to(x, (cfg.num_experts, T, H)), x.dtype,
    )                                                    # [E, T, H]
    oh = jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)  # [T, K, E]
    weights = (topw[..., None] * oh).sum(1)              # [T, E]
    return jnp.einsum("te,eth->th", weights.astype(x.dtype), out_all)


class RoutingStats:
    """Trace-time collector of one forward pass's routing: each expert
    layer adds its per-expert row counts, ``reduce`` folds them into the
    three numbers a step reports (engine/telemetry.py ``StepStats.moe_*``).
    ``valid`` [T] masks rows that are padding (a bucket's tail, an empty
    decode slot): they are multiplied, but no token needed them."""

    def __init__(self, valid: Optional[jax.Array] = None,
                 decode_rows: Optional[jax.Array] = None):
        self.valid = valid
        # the rows that are decode rows (a mixed step's chunk is not):
        # add_reads' sums are over these
        self.decode_rows = valid if decode_rows is None else decode_rows
        self.counts: List[jax.Array] = []
        self.read_names: Tuple[str, ...] = ()
        self.reads: Optional[jax.Array] = None

    def add(self, topi: jax.Array, num_experts: int) -> None:
        """``topi`` [T, K] expert of each assignment; one at or past
        ``num_experts`` (a layer that holds a share marks the assignments
        that land elsewhere so) is not counted: bincount drops it."""
        T, K = topi.shape
        weights = None
        if self.valid is not None:
            weights = jnp.repeat(self.valid.reshape(T).astype(jnp.int32), K)
        self.counts.append(
            jnp.bincount(topi.reshape(-1), weights=weights, length=num_experts)
        )

    def add_reads(self, **sums) -> None:
        """A forward pass over a latent cache adds, once, what its real
        decode rows read, summed over rows and layers, each sum under the
        name of its ``StepStats`` field (models/mla.py ``read_counters`` has
        the names and their order)."""
        self.read_names = tuple(sums)
        self.reads = jnp.stack(list(sums.values()))

    def reduce(self) -> jax.Array:
        """[3] float32: rows routed (T x K summed over layers; to the held
        experts where the layer holds a share), experts touched (summed over
        layers), the largest count on one expert; ``add_reads``' sums behind
        them, in the order they were named."""
        if not self.counts:
            # a family without experts that rides the readback for its
            # ``add_reads`` alone: the routing's three read zero
            out = jnp.zeros((3,), jnp.float32)
        else:
            c = jnp.stack(self.counts)                   # [L, E]
            out = jnp.stack(
                [c.sum(), (c > 0).sum(), c.max()]
            ).astype(jnp.float32)
        if self.reads is not None:
            out = jnp.concatenate([out, self.reads.astype(jnp.float32)])
        return out


def moe_ffn_grouped(
    p: Params, cfg: MoeConfig, x: jax.Array, routed=None,
    stats: Optional[RoutingStats] = None,
    matmul=grouped_matmul_reference,
    held: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Sparse exact serving path (replicated experts), one path for every
    token count: route, sort the T*K assignments by expert, ONE grouped
    multiplication for gate and up and one for down over the sorted rows,
    weighted combine back per token.

    ``held`` = (first, count): this layer holds ``count`` of the experts,
    from ``first`` on, and its stacks hold only those (one chip's share of a
    layer divided over chips). The router is the whole layer's: it chooses
    among all ``num_experts`` and its weights are normalised over all K
    chosen. Assignments that land on held experts are sorted to the front
    and multiplied; the others are multiplied by nothing and add nothing
    (their chips would have), and ``stats`` counts the held experts only.

    FLOPs are T*K*3HI vs the dense reference's T*E*3HI, and HBM reads touch
    each routed-to expert's weights once — at a decode batch about
    E(1-(1-K/E)^T) experts, not T*K private copies.

    ``routed`` overrides the router output (topw, topi) — the MLA family
    passes its DeepSeek-style routing through the same path. ``matmul`` is
    the grouped multiplication: the Pallas kernel on the kernel path
    (registry.forward_fn), its ``jax.lax.ragged_dot`` twin otherwise."""
    T, H = x.shape
    with jax.named_scope("moe_route"):
        topw, topi = routed if routed is not None else route(p, cfg, x)
    K = topi.shape[1]
    slots = p["w_gate"].shape[0]  # E, or E+R physical slots (replicas idle)
    counted = cfg.num_experts
    if held is not None:
        # held experts 0..count-1; every other assignment is group ``count``,
        # which sorts last and which no stack has
        first, counted = held
        topi = jnp.where(
            (topi >= first) & (topi < first + counted), topi - first, counted
        )
    if stats is not None:
        stats.add(topi, counted)
    with jax.named_scope("moe_sort"):
        flat = topi.reshape(-1)                          # [T*K]
        order = jnp.argsort(flat)                        # stable: by expert
        sizes = jnp.bincount(flat, length=slots).astype(jnp.int32)
        rows = x[order // K]                             # [T*K, H]
    with jax.named_scope("moe_experts"):
        act = matmul(rows, (p["w_gate"], p["w_up"]), sizes)   # [T*K, I]
        out = matmul(act, (p["w_down"],), sizes)              # [T*K, H]
        if held is not None:
            # rows past the last group were not multiplied: unspecified
            out = jnp.where((flat[order] < counted)[:, None], out, 0)
    with jax.named_scope("moe_combine"):
        back = out[jnp.argsort(order)].reshape(T, K, H)  # un-sort
        y = jnp.einsum(
            "tk,tkh->th", topw.astype(jnp.float32), back.astype(jnp.float32)
        )
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# the scored router + shared expert (DeepSeek-V3 / glm4_moe lineage): ONE
# definition for the families that route so (MlaConfig, SolarOpen2Config).
# It reads field names only: moe_scoring, n_group, topk_group, num_experts,
# num_experts_per_tok, norm_topk_prob, routed_scaling_factor, experts_held,
# num_shared_experts; parameters w_router, router_bias, w_e*, w_shared_*
# ---------------------------------------------------------------------------


def route_scored(p: Params, cfg, x: jax.Array):
    """Top-k router matching HF DeepseekV3TopkRouter semantics: sigmoid (V3)
    or softmax (V2) scores; SELECTION uses scores + the aux-free balancing
    bias (e_score_correction_bias) and optional group-limited top-k, while
    the combine WEIGHTS are the unbiased scores gathered at the selected
    indices, normalized then scaled. x [T, H] -> (weights [T,K] f32,
    idx [T,K])."""
    logits = (x.astype(jnp.float32) @ p["w_router"].astype(jnp.float32))
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    sel = scores
    bias = p.get("router_bias")
    if bias is not None:
        sel = sel + bias.astype(jnp.float32)
    if cfg.n_group > 1:
        T = sel.shape[0]
        G, Eg = cfg.n_group, cfg.num_experts // cfg.n_group
        group_scores = jax.lax.top_k(sel.reshape(T, G, Eg), 2)[0].sum(-1)
        _, gidx = jax.lax.top_k(group_scores, cfg.topk_group)        # [T, tg]
        gmask = jax.nn.one_hot(gidx, G, dtype=jnp.float32).sum(1)    # [T, G]
        emask = jnp.repeat(gmask, Eg, axis=-1)                       # [T, E]
        sel = jnp.where(emask > 0, sel, 0.0)  # HF masked_fill(~mask, 0.0)
    _, topi = jax.lax.top_k(sel, cfg.num_experts_per_tok)
    topw = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
        topw = topw / (topw.sum(-1, keepdims=True) + 1e-20)
    return topw * cfg.routed_scaling_factor, topi


# a layer's leaves that are stacked over the experts the chip holds
# (registry.expert_stack_leaves): a MoeConfig layer's, and those of a family
# whose layer is routed_shared_ffn's (mla, solar_open2)
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
ROUTED_SHARED_STACKS = ("w_egate", "w_eup", "w_edown")


def expert_stacks(p: Params) -> Params:
    """Expert stacks under the names moe.py's kernels expect."""
    return {k: p[name] for k, name in zip(EXPERT_STACKS, ROUTED_SHARED_STACKS)}


def routed_shared_ffn(
    p: Params, cfg, x: jax.Array, expert_fn=None, stats=None,
    matmul=grouped_matmul_reference,
) -> jax.Array:
    """Routed experts (the grouped path above fed by ``route_scored``, or a
    mesh-aware ``expert_fn`` injected by the registry for EP) + the always-on
    shared-expert SwiGLU. ``matmul`` is the grouped path's multiplication
    (the Pallas kernel where the registry turns it on). A family whose
    shared experts are COMBINED otherwise than by their sum says so in
    ``cfg.shared_expert_scale`` (an average of n: 1 / n on the one SwiGLU of
    their summed width), and names the shared branch for a device trace in
    ``cfg.shared_scope``; a family that states neither runs as it did."""
    routed = route_scored(p, cfg, x)
    if expert_fn is not None:
        y = expert_fn(expert_stacks(p), x, routed)
    else:
        y = moe_ffn_grouped(
            expert_stacks(p), cfg, x, routed=routed, stats=stats,
            matmul=matmul, held=cfg.experts_held,
        )
    if cfg.num_shared_experts > 0:
        scope = getattr(cfg, "shared_scope", None)
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            sg = jax.nn.silu((x @ p["w_shared_gate"]).astype(jnp.float32)).astype(x.dtype)
            shared = (sg * (x @ p["w_shared_up"])) @ p["w_shared_down"]
            scale = getattr(cfg, "shared_expert_scale", 1.0)
            y = y + (shared if scale == 1.0 else shared * scale)
    return y


# ---------------------------------------------------------------------------
# EP strategies
# ---------------------------------------------------------------------------


def moe_ffn_ep_psum(
    p: Params, cfg: MoeConfig, x: jax.Array, axis_name: str, routed=None
) -> jax.Array:
    """Inside shard_map: tokens replicated on ``axis_name``, expert-stacked
    weights sharded on their leading dim. Each shard computes its local
    experts' weighted contribution; psum combines. ``routed`` injects
    precomputed (topw, topi) — used by the MLA family's DeepSeek router,
    whose routing runs outside the shard_map."""
    T, H = x.shape
    E_loc = p["w_gate"].shape[0]
    me = jax.lax.axis_index(axis_name)
    topw, topi = routed if routed is not None else route(p, cfg, x)
    # EPLB: logical -> physical replica slots (tables replicated across
    # shards, so every shard computes the same assignment)
    topi = eplb_remap(p, topi)
    out_all = _expert_mlp(
        p["w_gate"], p["w_up"], p["w_down"],
        jnp.broadcast_to(x, (E_loc, T, H)), x.dtype,
    )                                                    # [E_loc, T, H]
    oh = jax.nn.one_hot(
        topi - me * E_loc, E_loc, dtype=jnp.float32
    )                                                    # [T, K, E_loc] (oob -> 0)
    weights = (topw[..., None] * oh).sum(1)              # [T, E_loc]
    local = jnp.einsum("te,eth->th", weights.astype(x.dtype), out_all)
    return jax.lax.psum(local, axis_name)


def moe_ffn_ep_a2a(
    p: Params, cfg: MoeConfig, x: jax.Array, axis_name: str
) -> jax.Array:
    """Inside shard_map: tokens SHARDED on ``axis_name`` [T_loc, H], experts
    sharded [E_loc, ...]. GShard-style capacity dispatch with two
    all-to-alls over ICI."""
    T, H = x.shape
    K = cfg.num_experts_per_tok
    ep = jax.lax.psum(1, axis_name)
    # E here is PHYSICAL (== logical when EPLB is off): the dispatch works
    # in physical slots; capacity stays a per-logical-expert budget
    E_loc = p["w_gate"].shape[0]
    E = E_loc * ep
    C = max(1, int(math.ceil(T * K / cfg.num_experts * cfg.capacity_factor)))

    topw, topi = route(p, cfg, x)                        # [T, K]
    topi = eplb_remap(p, topi)
    flat_i = topi.reshape(T * K)                         # expert per slot
    flat_w = topw.reshape(T * K)
    oh = jax.nn.one_hot(flat_i, E, dtype=jnp.float32)    # [T*K, E]
    pos = jnp.cumsum(oh, axis=0) - oh                    # queue position
    pos_sel = (pos * oh).sum(-1).astype(jnp.int32)       # [T*K]
    keep = pos_sel < C
    disp = oh * keep[:, None]                            # drop overflow
    slot_oh = jax.nn.one_hot(pos_sel, C, dtype=jnp.float32)
    # combine[t*k, e, c]: 1 where slot lands at (e, c)
    combine = disp[:, :, None] * slot_oh[:, None, :]     # [T*K, E, C]

    x_rep = jnp.repeat(x, K, axis=0)                     # [T*K, H] (slot-major)
    x_disp = jnp.einsum(
        "sec,sh->ech", combine.astype(x.dtype), x_rep
    )                                                    # [E, C, H]

    # ship each expert's buffer to its owner: tiled a2a keeps [E, C, H],
    # rows regrouped as (src_shard, local_expert)
    x_recv = jax.lax.all_to_all(
        x_disp, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    x_exp = (
        x_recv.reshape(ep, E_loc, C, H)
        .transpose(1, 0, 2, 3)
        .reshape(E_loc, ep * C, H)
    )
    y_exp = _expert_mlp(p["w_gate"], p["w_up"], p["w_down"], x_exp, x.dtype)
    y_send = (
        y_exp.reshape(E_loc, ep, C, H)
        .transpose(1, 0, 2, 3)
        .reshape(E, C, H)
    )
    y_recv = jax.lax.all_to_all(
        y_send, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    weighted = combine * flat_w[:, None, None]           # [T*K, E, C]
    y = jnp.einsum("sec,ech->sh", weighted.astype(x.dtype), y_recv)
    return y.reshape(T, K, H).sum(1)


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def layer_forward(
    p: Params,
    cfg: MoeConfig,
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    attend: AttendFn,
    layer_idx: int,
    ffn_fn=None,
    stats: Optional[RoutingStats] = None,
) -> jax.Array:
    """Same attention block as llama.layer_forward (cited there), with the
    layer's sliding window (if it has one) handed to ``attend``; the MLP is
    the sparse MoE. ``ffn_fn(p, cfg, x2d)`` overrides the FFN strategy."""
    h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    new_shape = h.shape[:-1]
    q = q.reshape(*new_shape, cfg.num_heads, cfg.head_dim)
    k = k.reshape(*new_shape, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(*new_shape, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # a full layer passes nothing, so it keeps the plain families' kernels
    window = cfg.window_for_layer(layer_idx)
    extra = {} if window is None else {"window": window}
    attn_out = attend(q, k, v, layer_idx, **extra)
    attn_out = attn_out.reshape(*new_shape, cfg.q_size)
    x = x + attn_out @ p["wo"]

    h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    lead = h.shape[:-1]
    h2d = h.reshape(-1, cfg.hidden_size)
    fn = ffn_fn if ffn_fn is not None else moe_ffn
    kw = {} if stats is None else {"stats": stats}
    y = fn(p, cfg, h2d, **kw).reshape(*lead, cfg.hidden_size)
    return x + y


def rope_tables(cfg: MoeConfig, positions: jax.Array, yarn: bool):
    """cos/sin [..., 1, d/2] of one layer kind: YaRN (full layers of a
    config that states a scaling factor) or plain rotary positions."""
    inv_freq, att = yarn_inv_freq(
        cfg.head_dim, cfg.rope_theta,
        cfg.rope_scaling_factor if yarn else 0.0,
        cfg.rope_original_max_position, cfg.rope_beta_fast,
        cfg.rope_beta_slow, cfg.rope_truncate,
    )
    if yarn and cfg.rope_attention_factor is not None:
        att = cfg.rope_attention_factor
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return (jnp.cos(angles) * att)[..., None, :], (jnp.sin(angles) * att)[..., None, :]


def forward(
    params: Params,
    cfg: MoeConfig,
    token_ids: jax.Array,
    positions: jax.Array,
    attend: AttendFn,
    ffn_fn=None,
    stats: Optional[RoutingStats] = None,
) -> jax.Array:
    """``stats`` (one-chip grouped path only) collects each layer's routing
    counts for the step's counters."""
    x = params["embed"][token_ids]
    # one table per layer KIND, built once per forward (two, not num_layers)
    tables = {}
    for i, layer in enumerate(params["layers"]):
        yarn = cfg.rope_scaling_factor > 1.0 and cfg.window_for_layer(i) is None
        if yarn not in tables:
            tables[yarn] = rope_tables(cfg, positions, yarn)
        cos, sin = tables[yarn]
        x = layer_forward(
            layer, cfg, x, cos, sin, attend, i, ffn_fn=ffn_fn, stats=stats
        )
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


lm_logits = llama.lm_logits
