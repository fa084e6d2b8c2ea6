"""Model-family registry: one place that maps a model config to its
(init, forward, lm_logits, partition-spec) functions so the engine stays
family-agnostic (reference analog: engine selection by ModelDeploymentCard
rather than hard-coded architectures).
"""

from __future__ import annotations

import dataclasses
from functools import partial

from jax.sharding import PartitionSpec as P

from ..ops.pallas_moe import grouped_matmul, grouped_matmul_reference
from ..parallel.mesh import AXIS_TP, shard_map
from . import (
    cohere2_moe, dots3_note, evabyte, falcon_h1, gemma, gptoss, llama,
    minicpm_sala, mla, moe, solar_open2,
)


def is_moe(cfg) -> bool:
    return isinstance(cfg, moe.MoeConfig)


def is_mla(cfg) -> bool:
    return isinstance(cfg, mla.MlaConfig)


def is_gptoss(cfg) -> bool:
    return isinstance(cfg, gptoss.GptOssConfig)


def is_gemma(cfg) -> bool:
    return isinstance(cfg, gemma.GemmaConfig)


def is_falcon_h1(cfg) -> bool:
    return isinstance(cfg, falcon_h1.FalconH1Config)


def is_solar_open2(cfg) -> bool:
    return isinstance(cfg, solar_open2.SolarOpen2Config)


def is_minicpm_sala(cfg) -> bool:
    return isinstance(cfg, minicpm_sala.MiniCpmSalaConfig)


def is_evabyte(cfg) -> bool:
    return isinstance(cfg, evabyte.EvaByteConfig)


def is_cohere2_moe(cfg) -> bool:
    return isinstance(cfg, cohere2_moe.Cohere2MoeConfig)


def is_dots3_note(cfg) -> bool:
    return isinstance(cfg, dots3_note.Dots3NoteConfig)


def supports_pp(cfg) -> bool:
    """Pipeline-parallel serving covers the dense llama family only: the
    stage placement stacks per-layer params homogeneously, which MoE expert
    stacks, MLA latent projections, gpt-oss/gemma windowed-attention extras,
    a family's slot state (a state-space mixer's, a linear-attention
    layer's), a ring with summaries and a parallel block with experts do not
    fit (parallel/pp_serving.py)."""
    return not (is_moe(cfg) or is_mla(cfg) or is_gptoss(cfg) or is_gemma(cfg)
                or is_falcon_h1(cfg) or is_solar_open2(cfg) or is_evabyte(cfg)
                or is_cohere2_moe(cfg) or is_minicpm_sala(cfg)
                or is_dots3_note(cfg))


def check_pp_supported(cfg) -> None:
    """One gate, one message: raised both at TpuEngine construction and at
    the pp_serving program builders, so a MoE/MLA/gpt-oss/gemma preset
    configured with pp>1 fails at the door with the fix spelled out instead
    of a KeyError deep in stacked-param placement."""
    if not supports_pp(cfg):
        raise ValueError(
            f"pp serving supports dense llama-family models only; "
            f"{type(cfg).__name__} (MoE/MLA/gpt-oss/gemma/falcon-h1/solar-open2/evabyte/"
            f"cohere2-moe/minicpm-sala/dots3-note) is not "
            f"stacked for pipeline stages — configure this preset with pp=1 "
            f"(use tp/sp/dp instead)"
        )


def counts_routing(cfg) -> bool:
    """Whether the family's one-chip forward takes a ``stats`` collector
    (moe.RoutingStats): the grouped expert path of MoeConfig, of an
    MlaConfig with experts, of SolarOpen2Config, of Cohere2MoeConfig
    (every layer routes) and of Dots3NoteConfig."""
    return (is_moe(cfg) or (is_mla(cfg) and cfg.num_experts > 0)
            or is_solar_open2(cfg) or is_cohere2_moe(cfg)
            or is_dots3_note(cfg))


def expert_stack_leaves(cfg) -> tuple:
    """The names of a layer's leaves that are stacked over the experts the
    chip holds (the family's ``EXPERT_STACKS``; none for a dense family): a
    step reads its top-k of such a leaf, not all of it."""
    return getattr(family(cfg), "EXPERT_STACKS", ())


def place_latent(cfg, *, tp=1, pp=1, sp=1, spec=False, lora=False,
                 kv_quantized=False, vision=False):
    """The layout of a latent WITHOUT an indexer, chosen where the
    parallelism is known (engine construction): on the one-chip text path a
    latent whose widths allow it (``MlaConfig.rows_capable``) is held as rows
    of 128 lanes, which the Pallas kernels can copy (``rows_layout``);
    everywhere else (``tp`` / ``pp`` / ``sp`` above 1, a speculative draft,
    LoRA, an 8-bit cache, vision) it stays one head of rank + rope lanes on
    the pure-JAX path, which shards and quantizes like any other head.
    Returns ``cfg``, or a copy with ``rows_layout`` set. A configuration that
    already states ``rows_layout`` (or has an indexer) is returned as it is:
    ``check_dsa_supported`` judges it."""
    if not is_mla(cfg) or cfg.latent_rows or not cfg.rows_capable:
        return cfg
    one_chip_text = (
        tp == 1 and pp == 1 and sp == 1
        and not (spec or lora or kv_quantized or vision)
    )
    return dataclasses.replace(cfg, rows_layout=True) if one_chip_text else cfg


def _refuse(what: str, refusals) -> None:
    """Raise for the first ``(asked, why)`` of ``refusals`` that was asked."""
    for hit, why in refusals:
        if hit:
            raise ValueError(f"{what} does not run with {why}")


def read_counters(cfg) -> tuple:
    """The ``StepStats`` fields a family's forward adds behind the routing's
    three on the step's readback (the family's ``read_counters``: a latent
    held as rows, a ring with summaries); () for the others."""
    own = getattr(family(cfg), "read_counters", None)
    return own(cfg) if own else ()


# What the one-chip text path refuses a family, by what was asked, for each of
# the two traits that bring a family here: a latent held as rows of 128 lanes
# (or a held share of the experts), and pages kept by layer kind. A family
# that is BOTH (models/dots3_note.py: two page groups, each a latent in rows)
# is answered from the two together, through ``_one_chip_text_path``: one
# list, whichever of the two checks the engine asks first.
_ROWS_WHY = {
    "tp": "tp > 1: the latent's rows are one head's and cannot shard "
          "on heads (the cache would be cut between its lanes), and a "
          "held share is already one chip's of a layer divided over "
          "chips (the exchange is not built)",
    "pp_sp": "pp / sp > 1: neither the wavefront nor the ring "
             "carries the rows layout (or a selection) from "
             "layer to layer",
    "spec": "a speculative draft: verify rows have no latent question in "
            "the attention seam yet",
    "lora": "LoRA: the family has no adapter path",
    "kv_quantized": "kv_dtype=int8: the latent kernels read bf16 rows; an "
                    "8-bit latent needs its scales a token",
    "vision": "vision: multimodal serving covers the dense family only",
}
_GROUPS_WHY = {
    "tp": "tp > 1: the groups' pools are not sharded by heads yet "
          "(a pool's sharding and its table a group), and a held "
          "share of the experts is already one chip's",
    "pp_sp": "pp / sp > 1: the wavefront stacks ONE pool over "
             "its stages and the ring attends one table; "
             "neither knows a group's table or its shift",
    "spec": "a speculative draft: its shadow cache is addressed by the "
            "ONE table of the main cache, and verify rows would read "
            "pages a window has let go",
    "lora": "LoRA: the family has no adapter path",
    "kv_quantized": "kv_dtype=int8: the scale rows are sized by ONE "
                    "pool's page count, and the family's cell holds its "
                    "pages to bf16",
    "vision": "vision: multimodal serving covers the dense family only",
    "transfer": "the KV transfer plane (disaggregation, evacuation): it "
                "moves the pages of ONE table by position over every "
                "page layer",
    "kvbm": "KVBM offload tiers: kvbm/layout.py counts num_layers pages "
            "a block hash, and a windowed group's pages are let go "
            "under a live request",
}


def _rows_or_share(cfg) -> bool:
    return bool(getattr(cfg, "latent_rows", False)
                or getattr(cfg, "experts_held", None))


def _one_chip_text_path(cfg, *, rows: bool, groups: bool, tp=1, pp=1, sp=1,
                        spec=False, lora=False, kv_quantized=False,
                        vision=False, transfer=False, kvbm=False) -> None:
    """Raise for the first thing asked that a trait of ``cfg`` refuses, with
    every such trait's reason (``rows`` / ``groups``: the traits the caller
    answers for; a family that has both is answered for both)."""
    traits = []
    if rows:
        traits.append((
            "a latent held as rows of 128 lanes (learned sparse attention, "
            f"or none) / a held share of the experts ({type(cfg).__name__})",
            _ROWS_WHY,
        ))
    if groups:
        traits.append((
            f"pages kept by layer kind ({type(cfg).__name__})", _GROUPS_WHY
        ))
    asked = [
        ("tp", tp > 1), ("pp_sp", pp > 1 or sp > 1), ("spec", spec),
        ("lora", lora), ("kv_quantized", kv_quantized), ("vision", vision),
        ("transfer", transfer), ("kvbm", kvbm),
    ]
    _refuse(" + ".join(what for what, _ in traits), [
        (hit, "; ".join(dict.fromkeys(
            why[key] for _, why in traits if key in why)))
        for key, hit in asked if any(key in why for _, why in traits)
    ])


def check_dsa_supported(cfg, *, tp=1, pp=1, sp=1, spec=False, lora=False,
                        kv_quantized=False, vision=False) -> None:
    """A configuration whose latent is held as rows of 128 lanes (learned
    sparse attention, or a latent without an indexer that states
    ``rows_layout``: the family's ``latent_rows``), or one that holds a share
    of its experts, runs on the one-chip text path; what it cannot do yet is
    refused here, at engine construction, each with its reason. A latent
    without an indexer that states no layout never comes here as rows:
    ``place_latent`` leaves it one head wherever a refusal would hit. A
    family that also keeps pages by layer kind is refused what EITHER trait
    refuses, with both reasons (``_one_chip_text_path``)."""
    if not _rows_or_share(cfg):
        return
    _one_chip_text_path(
        cfg, rows=True, groups=len(page_groups(cfg)) > 1, tp=tp, pp=pp,
        sp=sp, spec=spec, lora=lora, kv_quantized=kv_quantized, vision=vision,
    )


def state_spec(cfg) -> tuple:
    """Per-layer arrays ONE SLOT holds beside the paged keys, as (name,
    shape, dtype): a state-space mixer's recurrent state and its
    convolution's tail (``falcon_h1.state_spec``), a linear-attention
    layer's matrix state and its tail (``solar_open2.state_spec``) or its
    matrix state alone (``minicpm_sala.state_spec``); () for
    every family whose only state is pages. engine/state_cache.py builds the
    store from it, one array a layer of ``state_layers``, and the step
    programs take and return it only where it is not empty. The family's
    module answers (its ``state_spec``), as it answers the three questions
    below where it has something to say."""
    own = getattr(family(cfg), "state_spec", None)
    return own(cfg) if own else ()


def page_layers(cfg) -> tuple:
    """The model layers that keep PAGES of keys and values, in order: every
    layer, except where a family's layers are of kinds that keep different
    state (``solar_open2``: its softmax-attention layers only). The engine
    allocates one pair of page arrays a layer named here and ``attend``
    finds a layer's pair by its place in this tuple (``layer_index``). One
    block table still serves every page layer."""
    own = getattr(family(cfg), "page_layers", None)
    return own(cfg) if own else tuple(range(cfg.num_layers))


def page_groups(cfg) -> tuple:
    """The page layers in GROUPS, each ``(layers, lifetime)``: ``lifetime``
    None (a page lives as long as its request) or a window in positions (a
    page is let go once it lies wholly behind ``position - window``). A
    group has page arrays, a ``BlockAllocator`` and a table a row of its
    own; the group that lives as long as the request comes first. Every
    family answers ONE group of all its ``page_layers`` with lifetime None,
    and the engine builds for it what it always built, unless its module
    says otherwise (``cohere2_moe.page_groups``: pages by layer kind)."""
    own = getattr(family(cfg), "page_groups", None)
    return own(cfg) if own else ((page_layers(cfg), None),)


def page_shapes(cfg) -> tuple:
    """A token's shape in each page layer's two arrays, in ``page_layers``'
    order: ``((k rows, k lanes), (v rows, v lanes))`` a layer. Every family
    answers ``(num_kv_heads, head_dim)`` for both arrays of every layer, and
    the engine allocates what it always did, unless its module says
    otherwise (``dots3_note.page_shapes``: a page GROUP's arrays take the
    shape its layers need: the latent's own rows in the first, the one tile
    a step reads in the second)."""
    own = getattr(family(cfg), "page_shapes", None)
    if own:
        return own(cfg)
    token = (cfg.num_kv_heads, cfg.head_dim)
    return ((token, token),) * len(page_layers(cfg))


def pooled_keys(cfg):
    """What a page layer keeps BESIDE its pages, or None (every other
    family): an ``ops/attention.InfLlmQuery`` where it keeps ONE POOLED KEY a
    page a kv head, by block id (the family's ``pooled_keys``: block-sparse
    attention that chooses its blocks from them, ``minicpm_sala``). The
    engine then gives the K pool a row a block id above the requests' pages
    (``_init_caches``; ops/attention.py has the layout), holds the page to
    the pooled keys' stride, and the attention seam writes and reads them."""
    own = getattr(family(cfg), "pooled_keys", None)
    return own(cfg) if own else None


def state_layers(cfg) -> tuple:
    """The model layers that keep SLOT STATE (``state_spec``), in order: none
    for a family without it, every layer for Falcon-H1 (its mixer runs beside
    attention in each), the linear-attention layers for ``solar_open2``."""
    if not state_spec(cfg):
        return ()
    own = getattr(family(cfg), "state_layers", None)
    return own(cfg) if own else tuple(range(cfg.num_layers))


def layer_index(layers: tuple, num_layers: int):
    """model layer -> its place among ``layers`` (what a seam indexes its
    store by), or None where every layer is named: the seam then uses the
    model's index as it always did, and nothing is wrapped."""
    if layers == tuple(range(num_layers)):
        return None
    return {l: i for i, l in enumerate(layers)}


def state_prefix(cfg) -> str:
    """The prefix of the ``StepStats`` counters a family's recurrence is
    counted under (``<prefix>_rows_updated``, ``_tokens_scanned``,
    ``_decode_steps``): engine ``_count_state``. The family's
    ``STATE_PREFIX``."""
    return getattr(family(cfg), "STATE_PREFIX", "ssm")


def mixers(cfg, use_pallas: bool = False, interpret: bool = False) -> tuple:
    """The two mixing functions the engine's second seam is built from, for
    a family with ``state_spec``: ``mix_chunk(p, cfg, *inputs, *state,
    n_real) -> (y, *state')`` (a run of one request's tokens from its slot's
    arrays, in ``state_spec``'s order) and ``mix_rows(p, cfg, *inputs,
    *states, live) -> (y, *states')`` (one token a live row, its recurrence
    the family's Pallas launch where ``use_pallas``)."""
    fam = family(cfg)
    return fam.mix_chunk, partial(
        fam.mix_rows, update=fam.state_update(use_pallas, interpret)
    )


def window_ring(cfg):
    """Positions a request's PAGES cover before they are written again, or
    None (every other family: a page lives as long as its request). With a
    ring, position ``p`` lives in entry ``(p mod ring) // page`` of the
    row's table, a request never holds more than ``ring / page`` pages, and
    what it keeps of a closed window is ``summary_spec``."""
    own = getattr(family(cfg), "window_ring", None)
    return own(cfg) if own else None


def summary_spec(cfg) -> tuple:
    """The third answer beside ``page_layers`` and ``state_spec``: what ONE
    CLOSED WINDOW of a request keeps a layer, as (name, shape, dtype)
    (``evabyte.summary_spec``: a summary key and value a chunk a head); ()
    for every family without a ``window_ring``. The engine keeps them in
    summary blocks, one a window, taken when the window opens and released
    with the request (engine ``summary_allocator``)."""
    own = getattr(family(cfg), "summary_spec", None)
    return own(cfg) if own else ()


def prefix_reusable(cfg) -> bool:
    """Whether a block hash restores everything a request needs of its
    prefix. Pages, yes; a recurrent state is not kept per block, so a family
    with ``state_spec`` declines prefix hits (the prompt prefills whole) and
    neither registers nor publishes its blocks as reusable. Nor does a ring:
    a closed window's pages are gone, and its summaries are not kept by
    block hash (they could be: a summary is a function of its own block)."""
    return not state_spec(cfg) and window_ring(cfg) is None


def check_eva_supported(cfg, *, tp=1, pp=1, sp=1, spec=False, lora=False,
                        kv_quantized=False, vision=False, transfer=False,
                        kvbm=False) -> None:
    """A family whose pages are a ring with summaries by window
    (``window_ring``) runs on the one-chip text path; what it cannot do yet
    is refused here, at engine construction (``transfer``: where the
    transfer plane is asked for), each with its reason."""
    if window_ring(cfg) is None:
        return
    what = f"a ring of pages with summaries by window ({type(cfg).__name__})"
    refusals = [
        (tp > 1, "tp > 1: the summary blocks are not sharded by heads yet "
                 "(param_specs, the pool's sharding over the two learned "
                 "vectors a head)"),
        (pp > 1 or sp > 1, "pp / sp > 1: neither the wavefront nor the ring "
                           "attention carries a window's summaries from "
                           "stage to stage or shard to shard"),
        (spec, "a speculative draft: verify rows would write pages of a "
               "window they may not reach, and a rejected token's summary "
               "would have to be rolled back"),
        (lora, "LoRA: the family has no adapter path"),
        (kv_quantized, "kv_dtype=int8: a summary is one key of many a "
                       "softmax reads as often as any; an 8-bit summary is "
                       "not calibrated (the family's cell holds it to bf16)"),
        (vision, "vision: multimodal serving covers the dense family only"),
        (transfer, "the KV transfer plane (disaggregation, evacuation): it "
                   "moves pages by position and knows neither a ring nor "
                   "summary blocks"),
        (kvbm, "KVBM offload tiers: they keep pages by block hash, and a "
               "ring's pages are overwritten under a live request"),
    ]
    _refuse(what, refusals)


def check_groups_supported(cfg, *, tp=1, pp=1, sp=1, spec=False, lora=False,
                            kv_quantized=False, vision=False, transfer=False,
                            kvbm=False) -> None:
    """A family whose pages are kept by layer kind (more than one
    ``page_groups``) runs on the one-chip text path; what it cannot do yet
    is refused here, at engine construction (``transfer``: where the
    transfer plane is asked for), each with its reason; a family that also
    holds a latent as rows (or a share) is refused what EITHER trait refuses
    (``_one_chip_text_path``, the list ``check_dsa_supported`` answers from)."""
    groups = page_groups(cfg)
    if len(groups) == 1:
        return
    if groups[0][1] is not None:
        raise ValueError(
            f"{type(cfg).__name__}: the first page group lives as long as "
            "the request (the engine's own table and allocator are its)"
        )
    _one_chip_text_path(
        cfg, rows=_rows_or_share(cfg), groups=True, tp=tp, pp=pp, sp=sp,
        spec=spec, lora=lora, kv_quantized=kv_quantized, vision=vision,
        transfer=transfer, kvbm=kvbm,
    )


def check_state_supported(cfg, *, tp=1, pp=1, sp=1, spec=False, lora=False,
                          kv_quantized=False, vision=False, transfer=False,
                          kvbm=False) -> None:
    """A family that keeps slot state beside its pages (``state_spec``) runs
    on the one-chip text path; what it cannot do yet is refused here, at
    engine construction (``transfer``: where the transfer plane is asked
    for), each with its reason."""
    if not state_spec(cfg):
        return
    what = f"slot state ({type(cfg).__name__})"
    refusals = [
        (tp > 1, "tp > 1: the recurrence's heads (and a mixer's groups) are "
                 "not sharded yet (param_specs, the state's sharding)"),
        (pp > 1 or sp > 1, "pp / sp > 1: neither the wavefront nor the ring "
                           "carries the recurrent state from stage to stage "
                           "or shard to shard"),
        (spec, "a speculative draft: verify rows would need the state rolled "
               "back to the last accepted token"),
        (lora, "LoRA: the family has no adapter path"),
        (kv_quantized, "kv_dtype=int8: the family's cell runs bf16 pages and "
                       "a float32 state; an 8-bit cache is not calibrated "
                       "for it"),
        (vision, "vision: multimodal serving covers the dense family only"),
        (transfer, "the KV transfer plane (disaggregation, evacuation): it "
                   "moves pages and knows no slot state"),
        (kvbm, "KVBM offload tiers: they keep pages by block hash and know "
               "no slot state"),
    ]
    _refuse(what, refusals)


def family(cfg):
    if is_dots3_note(cfg):
        return dots3_note
    if is_cohere2_moe(cfg):
        return cohere2_moe
    if is_evabyte(cfg):
        return evabyte
    if is_minicpm_sala(cfg):
        return minicpm_sala
    if is_solar_open2(cfg):
        return solar_open2
    if is_falcon_h1(cfg):
        return falcon_h1
    if is_mla(cfg):
        return mla
    if is_gptoss(cfg):
        return gptoss
    if is_gemma(cfg):
        return gemma
    return moe if is_moe(cfg) else llama


def init_params(rng, cfg):
    return family(cfg).init_params(rng, cfg)


def _ep_psum_shard_map(mesh, weight_specs, kernel, n_extra_args):
    """THE shard_map construction site for every family's EP path:
    expert-stacked weights sharded per ``weight_specs``, tokens (and any
    precomputed routing) replicated, ``kernel`` per shard with a psum
    combine inside. One site = the collective shape cannot drift between
    the MoeConfig, MLA, and gpt-oss families. ``n_extra_args``: 0 for
    kernel(shard_params, x), 1 for kernel(shard_params, x, routed)."""
    extra = ((P(), P()),) * n_extra_args
    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(weight_specs, P(), *extra),
        out_specs=P(),
        check_vma=False,
    )


def forward_fn(cfg, mesh=None, use_pallas: bool = False,
               interpret: bool = False):
    """Forward pass for the family. For MoE the FFN strategy is picked here
    so serving never pays dense all-expert FLOPs (ADVICE r2):

    - experts replicated (no mesh / tp==1): the token-sorted grouped path
      (moe_ffn_grouped, T*K routed rows, each touched expert read once) for
      every token count; ``use_pallas`` picks its multiplication — the
      Pallas kernel ``moe_grouped_matmul`` (``interpret``: off-TPU tests)
      or the ``jax.lax.ragged_dot`` twin
    - experts sharded over tp (EP rides the TP axis): shard_map'd
      moe_ffn_ep_psum — each shard computes only its local experts, one
      psum combines (same collective as a TP row matmul)
    """
    if is_falcon_h1(cfg):
        return falcon_h1.forward
    if is_evabyte(cfg):
        return evabyte.forward
    if is_minicpm_sala(cfg):
        return minicpm_sala.forward
    if is_solar_open2(cfg) or is_cohere2_moe(cfg) or is_dots3_note(cfg):
        # the held (or replicated) experts' grouped path, its multiplication
        # as for MlaConfig below; tp > 1 is refused at construction
        fwd = family(cfg).forward
        if use_pallas:
            return partial(
                fwd, matmul=partial(grouped_matmul, interpret=interpret)
            )
        return fwd
    if is_gptoss(cfg):
        if mesh is None or mesh.shape.get(AXIS_TP, 1) == 1:
            return gptoss.forward

        # EP: gpt-oss's own expert kernel (fused biased gate_up, clamped
        # swiglu) sharded on the expert dim; router replicated outside
        gu_specs = {
            "w_gateup": P(AXIS_TP, None, None),
            "b_gateup": P(AXIS_TP, None),
            "w_edown": P(AXIS_TP, None, None),
            "b_edown": P(AXIS_TP, None),
        }

        def gptoss_expert_fn(ep, x, routed):
            fn = _ep_psum_shard_map(
                mesh, gu_specs,
                lambda sp, sx, srouted: gptoss.experts_ep_psum(
                    sp, cfg, sx, srouted, AXIS_TP
                ),
                1,
            )
            return fn(ep, x, routed)

        return partial(gptoss.forward, expert_fn=gptoss_expert_fn)
    if is_mla(cfg):
        if cfg.num_experts == 0 or mesh is None or mesh.shape.get(AXIS_TP, 1) == 1:
            # token-sorted grouped path (exact, sparse) on replicated (or
            # held) experts; its multiplication as for MoeConfig below
            if use_pallas and cfg.num_experts > 0:
                return partial(
                    mla.forward,
                    matmul=partial(grouped_matmul, interpret=interpret),
                )
            return mla.forward

        # EP: expert stacks shard on the expert dim over the tp axis (same
        # devices as attention TP); the DeepSeek router runs OUTSIDE the
        # shard_map (it is replicated), each shard computes its local
        # experts' contribution, one psum combines — identical collective
        # shape to the MoeConfig path. Specs come from param_specs (one
        # source of truth with how the engine placed the weights), remapped
        # to the kernel's w_gate/w_up/w_down names (mla.expert_params).
        layer_specs = param_specs(cfg)["layer"]
        weight_specs = {
            "w_gate": layer_specs["w_egate"],
            "w_up": layer_specs["w_eup"],
            "w_down": layer_specs["w_edown"],
        }

        def mla_expert_fn(ep, x, routed):
            fn = _ep_psum_shard_map(
                mesh, weight_specs,
                lambda sp, sx, srouted: moe.moe_ffn_ep_psum(
                    sp, cfg, sx, AXIS_TP, routed=srouted
                ),
                1,
            )
            return fn(ep, x, routed)

        return partial(mla.forward, expert_fn=mla_expert_fn)
    if is_gemma(cfg):
        # dense family: megatron TP rides GSPMD like llama; sliding-window
        # layers use the same paged ``window`` path as gpt-oss
        return gemma.forward
    if not is_moe(cfg):
        return llama.forward
    if mesh is None or mesh.shape.get(AXIS_TP, 1) == 1:
        matmul = grouped_matmul_reference
        if use_pallas:
            matmul = partial(grouped_matmul, interpret=interpret)
        return partial(
            moe.forward, ffn_fn=partial(moe.moe_ffn_grouped, matmul=matmul)
        )

    # one source of truth for the expert layout: the same specs the engine
    # places the params with (below)
    layer_specs = param_specs(cfg)["layer"]
    ep_keys = ("w_router", "w_gate", "w_up", "w_down")
    if getattr(cfg, "redundant_experts", 0) > 0:
        # EPLB remap tables ride into the shard_map replicated (every shard
        # must compute the same logical->physical assignment)
        ep_keys = ep_keys + ("eplb_slots", "eplb_nrep")
    ep_specs = (
        {k: layer_specs.get(k, P()) for k in ep_keys}, P()
    )

    def ffn(p, _cfg, x):
        sub = {k: p[k] for k in ep_keys}
        fn = _ep_psum_shard_map(
            mesh, ep_specs[0],
            lambda sp, sx: moe.moe_ffn_ep_psum(sp, _cfg, sx, AXIS_TP),
            0,
        )
        return fn(sub, x)

    return partial(moe.forward, ffn_fn=ffn)


def lm_logits_fn(cfg):
    return family(cfg).lm_logits


def param_specs(cfg) -> dict:
    """name -> PartitionSpec for top-level and per-layer params.

    Dense family: megatron TP (parallel/mesh.param_specs_llama). MoE: the
    expert-stacked FFN weights shard on the EXPERT dim over the tp axis
    (EP rides the same devices as attention TP); GSPMD inserts the psum at
    the expert-contraction einsum. The router is tiny and replicated.
    """
    top = {
        "embed": P(None, AXIS_TP),
        "final_norm": P(None),
        "lm_head": P(None, AXIS_TP),
    }
    layer = {
        "wq": P(None, AXIS_TP),
        "wk": P(None, AXIS_TP),
        "wv": P(None, AXIS_TP),
        "wo": P(AXIS_TP, None),
        "bq": P(AXIS_TP),
        "bk": P(AXIS_TP),
        "bv": P(AXIS_TP),
    }
    if is_evabyte(cfg):
        # tp > 1 is refused at construction (check_eva_supported): the
        # dense family's specs, the two learned vectors a head follow them
        layer.update({
            "w_gate": P(None, AXIS_TP), "w_up": P(None, AXIS_TP),
            "w_down": P(AXIS_TP, None),
            "mu": P(AXIS_TP, None), "phi": P(AXIS_TP, None),
        })
        return {"top": top, "layer": layer, "default": P()}
    if is_cohere2_moe(cfg) or is_dots3_note(cfg):
        # tp > 1 is refused at construction (check_groups_supported): the
        # attention's specs are the dense family's, everything else replicates
        return {"top": top, "layer": layer, "default": P()}
    if is_solar_open2(cfg):
        # tp > 1 is refused at construction (check_state_supported): the
        # attention layers' specs are the dense family's, their output gate
        # follows the heads, everything else replicates
        layer["w_gate"] = P(None, AXIS_TP)
        return {"top": top, "layer": layer, "default": P()}
    if is_minicpm_sala(cfg):
        # tp > 1 is refused at construction (check_state_supported): the
        # dense family's specs, both kinds of layer's output gate follows
        # the heads
        layer.update({
            "w_gate": P(None, AXIS_TP), "w_up": P(None, AXIS_TP),
            "w_down": P(AXIS_TP, None), "w_ogate": P(None, AXIS_TP),
        })
        return {"top": top, "layer": layer, "default": P()}
    if is_gptoss(cfg):
        layer.update({
            "bo": P(None),
            "sinks": P(None),
            "w_router": P(),
            "b_router": P(),
            "w_gateup": P(AXIS_TP, None, None),
            "b_gateup": P(AXIS_TP, None),
            "w_edown": P(AXIS_TP, None, None),
            "b_edown": P(AXIS_TP, None),
        })
        return {"top": top, "layer": layer, "default": P()}
    if is_mla(cfg):
        # q heads shard over TP (head-stacked w_uk/w_uv, column-parallel
        # w_uq/wq, row-parallel wo); the shared latent projections and the
        # 1-head latent KV stay replicated.
        layer.update({
            "wq": P(None, AXIS_TP),
            "w_uq": P(None, AXIS_TP),
            "w_dq": P(),
            "w_dkv": P(),
            "w_uk": P(AXIS_TP, None, None),
            "w_uv": P(AXIS_TP, None, None),
            "wo": P(AXIS_TP, None),
            "w_router": P(),
            "w_shared_gate": P(None, AXIS_TP),
            "w_shared_up": P(None, AXIS_TP),
            "w_shared_down": P(AXIS_TP, None),
        })
        # dense-layer FFN (and first_dense_layers of MoE models) keep the
        # megatron column/row specs; expert stacks live under their own
        # names (w_e*) and shard on the EXPERT dim over tp
        layer.update({
            "w_gate": P(None, AXIS_TP),
            "w_up": P(None, AXIS_TP),
            "w_down": P(AXIS_TP, None),
            "w_egate": P(AXIS_TP, None, None),
            "w_eup": P(AXIS_TP, None, None),
            "w_edown": P(AXIS_TP, None, None),
        })
    elif is_moe(cfg):
        layer.update({
            "w_router": P(None, None),
            "w_gate": P(AXIS_TP, None, None),
            "w_up": P(AXIS_TP, None, None),
            "w_down": P(AXIS_TP, None, None),
        })
    else:
        layer.update({
            "w_gate": P(None, AXIS_TP),
            "w_up": P(None, AXIS_TP),
            "w_down": P(AXIS_TP, None),
        })
    return {"top": top, "layer": layer, "default": P()}


def kv_cache_spec(cfg, tp: int = 1) -> P:
    """Paged-KV sharding for the family. Caches shard kv_heads over TP when
    they divide evenly; otherwise (MQA / MLA-latent 1-head caches, or GQA
    with fewer kv heads than TP shards) the cache replicates — the layout
    real MLA deployments use, and the same condition the engine's Pallas
    eligibility check uses."""
    from ..parallel import mesh as meshlib

    kvh = getattr(cfg, "num_kv_heads", 0)
    if kvh == 1 or (tp > 1 and kvh % tp != 0):
        return P(None, None, None, None)
    return meshlib.kv_cache_spec()


def kv_scale_spec(cfg, tp: int = 1) -> P:
    """Sharding for the int8 cache's per-block-per-kv-head scale rows
    ([num_blocks, kv_heads] f32): the kv-head dim follows the cache payload
    — sharded over TP exactly when kv_cache_spec shards kv_heads, replicated
    otherwise (MQA / MLA-latent / non-dividing GQA). One condition, two
    specs, so payload and scales can never shard apart."""
    if kv_cache_spec(cfg, tp) == P(None, None, None, None):
        return P(None, None)
    from ..parallel.mesh import AXIS_TP as _tp_axis

    return P(None, _tp_axis)
