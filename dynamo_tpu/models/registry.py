"""Model-family registry: one place that maps a model config to its
(init, forward, lm_logits, partition-spec) functions so the engine stays
family-agnostic (reference analog: engine selection by ModelDeploymentCard
rather than hard-coded architectures).
"""

from __future__ import annotations

import dataclasses
from functools import partial

from jax.sharding import PartitionSpec as P

from ..parallel import mesh as meshlib
from ..parallel.mesh import AXIS_TP
from . import (
    cohere2_moe, dots3_note, evabyte, falcon_h1, gemma, gptoss, llama,
    minicpm_sala, mla, moe, ouro, solar_open2,
)

# Every family, once. A module names its configuration class (``CONFIG``),
# ``init_params`` / ``forward`` / ``lm_logits``, and whatever else it has to
# say of the questions below; where it says nothing the registry answers what
# the dense family needs (docs/architecture.md "Adding a family").
FAMILIES = (
    llama, moe, mla, gptoss, gemma, falcon_h1, solar_open2, evabyte,
    cohere2_moe, minicpm_sala, dots3_note, ouro,
)


def family(cfg):
    """The module of ``cfg``'s family: the first class of its MRO that a
    module of ``FAMILIES`` names, so a subclass of ``LlamaConfig`` with a
    module of its own finds that module, and a subclass without one its
    parent's."""
    for cls in type(cfg).__mro__:
        for module in FAMILIES:
            if module.CONFIG is cls:
                return module
    raise TypeError(f"{type(cfg).__name__} is no family's configuration "
                    "(models/registry.FAMILIES)")


def _ask(cfg, name, default=None):
    """The family's answer to ``name`` (a function of ``cfg``, or a constant),
    or ``default`` where its module has none."""
    own = getattr(family(cfg), name, default)
    return own(cfg) if callable(own) else own


def supports_pp(cfg) -> bool:
    """Pipeline-parallel serving covers the dense llama family only
    (``llama.SUPPORTS_PP``): the stage placement stacks per-layer params
    homogeneously, which expert stacks, latent projections, windowed-attention
    extras, a family's slot state, a ring with summaries and a parallel block
    with experts do not fit (parallel/pp_serving.py)."""
    return getattr(family(cfg), "SUPPORTS_PP", False)


def check_pp_supported(cfg) -> None:
    """One gate, one message: raised both at TpuEngine construction and at
    the pp_serving program builders, so a preset of another family configured
    with pp>1 fails at the door with the fix spelled out instead of a
    KeyError deep in stacked-param placement."""
    if not supports_pp(cfg):
        raise ValueError(
            f"pp serving supports dense llama-family models only; "
            f"{type(cfg).__name__} is not stacked for pipeline stages — "
            f"configure this preset with pp=1 (use tp/sp/dp instead)"
        )


def counts_routing(cfg) -> bool:
    """Whether the family's one-chip forward takes a ``stats`` collector
    (moe.RoutingStats): a grouped expert path."""
    return _ask(cfg, "counts_routing", False)


def pallas_auto(cfg) -> bool:
    """Whether the family rides the engine's Pallas AUTO rule where its
    shapes allow (``TpuEngine._pallas_auto_ok``); a module says no with
    ``PALLAS_AUTO = False`` and its reason."""
    return getattr(family(cfg), "PALLAS_AUTO", True)


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
    has no such choice (no ``rows_capable``), or already states
    ``rows_layout`` (or has an indexer), is returned as it is:
    ``check_supported`` judges it."""
    if not getattr(cfg, "rows_capable", False) or cfg.latent_rows:
        return cfg
    one_chip_text = (
        tp == 1 and pp == 1 and sp == 1
        and not (spec or lora or kv_quantized or vision)
    )
    return dataclasses.replace(cfg, rows_layout=True) if one_chip_text else cfg


def read_counters(cfg) -> tuple:
    """The ``StepStats`` fields a family's forward adds behind the routing's
    three on the step's readback (the family's ``read_counters``: a latent
    held as rows, a ring with summaries); () for the others."""
    return _ask(cfg, "read_counters", ())


# What a family cannot do yet, as ``trait -> what was asked -> why``. The
# first five traits are FOUND from what the family already declares: slot
# state beside the pages (``state_spec``), a ring of pages with summaries
# (``window_ring``), a latent held as rows of 128 lanes or a held share of
# the experts (the configuration's ``latent_rows`` / ``experts_held``), pages
# kept by layer kind (more than one of ``page_groups``), which put it on the
# one-chip text path, and page slots that outnumber the layers
# (``page_passes`` above 1), which refuses no ``tp``. A family of several
# (models/dots3_note.py: rows and groups; models/solar_open2.py: state and a
# share) is answered from all of them together. The rest a module STATES: its
# ``TRAITS``, and ``ADAPTERS`` where it has a LoRA path (``llama`` alone).
_NO_ADAPTERS = "LoRA: the family has no adapter path"
_DENSE_VISION = "vision: multimodal serving covers the dense family only"
_WHAT = {
    "state": "slot state ({name})",
    "ring": "a ring of pages with summaries by window ({name})",
    "rows": "a latent held as rows of 128 lanes (learned sparse attention, "
            "or none) / a held share of the experts ({name})",
    "groups": "pages kept by layer kind ({name})",
    "passes": "page slots that outnumber the layers, one a (pass, layer) "
              "({name})",
}
_WHY = {
    "state": {
        "tp": "tp > 1: the recurrence's heads (and a mixer's groups) are "
              "not sharded yet (param_specs, the state's sharding)",
        "pp_sp": "pp / sp > 1: neither the wavefront nor the ring "
                 "carries the recurrent state from stage to stage "
                 "or shard to shard",
        "spec": "a speculative draft: verify rows would need the state rolled "
                "back to the last accepted token",
        "lora": _NO_ADAPTERS,
        "kv_quantized": "kv_dtype=int8: the family's cell runs bf16 pages and "
                        "a float32 state; an 8-bit cache is not calibrated "
                        "for it",
        "vision": _DENSE_VISION,
        "transfer": "the KV transfer plane (disaggregation, evacuation): it "
                    "moves pages and knows no slot state",
        "kvbm": "KVBM offload tiers: they keep pages by block hash and know "
                "no slot state",
    },
    "ring": {
        "tp": "tp > 1: the summary blocks are not sharded by heads yet "
              "(param_specs, the pool's sharding over the two learned "
              "vectors a head)",
        "pp_sp": "pp / sp > 1: neither the wavefront nor the ring "
                 "attention carries a window's summaries from "
                 "stage to stage or shard to shard",
        "spec": "a speculative draft: verify rows would write pages of a "
                "window they may not reach, and a rejected token's summary "
                "would have to be rolled back",
        "lora": _NO_ADAPTERS,
        "kv_quantized": "kv_dtype=int8: a summary is one key of many a "
                        "softmax reads as often as any; an 8-bit summary is "
                        "not calibrated (the family's cell holds it to bf16)",
        "vision": _DENSE_VISION,
        "transfer": "the KV transfer plane (disaggregation, evacuation): it "
                    "moves pages by position and knows neither a ring nor "
                    "summary blocks",
        "kvbm": "KVBM offload tiers: they keep pages by block hash, and a "
                "ring's pages are overwritten under a live request",
    },
    "rows": {
        "tp": "tp > 1: the latent's rows are one head's and cannot shard "
              "on heads (the cache would be cut between its lanes), and a "
              "held share is already one chip's of a layer divided over "
              "chips (the exchange is not built)",
        "pp_sp": "pp / sp > 1: neither the wavefront nor the ring "
                 "carries the rows layout (or a selection) from "
                 "layer to layer",
        "spec": "a speculative draft: verify rows have no latent question in "
                "the attention seam yet",
        "lora": _NO_ADAPTERS,
        "kv_quantized": "kv_dtype=int8: the latent kernels read bf16 rows; an "
                        "8-bit latent needs its scales a token",
        "vision": _DENSE_VISION,
    },
    "groups": {
        "tp": "tp > 1: the groups' pools are not sharded by heads yet "
              "(a pool's sharding and its table a group), and a held "
              "share of the experts is already one chip's",
        "pp_sp": "pp / sp > 1: the wavefront stacks ONE pool over "
                 "its stages and the ring attends one table; "
                 "neither knows a group's table or its shift",
        "spec": "a speculative draft: its shadow cache is addressed by the "
                "ONE table of the main cache, and verify rows would read "
                "pages a window has let go",
        "lora": _NO_ADAPTERS,
        "kv_quantized": "kv_dtype=int8: the scale rows are sized by ONE "
                        "pool's page count, and the family's cell holds its "
                        "pages to bf16",
        "vision": _DENSE_VISION,
        "transfer": "the KV transfer plane (disaggregation, evacuation): it "
                    "moves the pages of ONE table by position over every "
                    "page layer",
        "kvbm": "KVBM offload tiers: kvbm/layout.py counts num_layers pages "
                "a block hash, and a windowed group's pages are let go "
                "under a live request",
    },
    "passes": {
        "pp_sp": "pp / sp > 1: the wavefront stacks num_layers pools over "
                 "its stages and runs them once (parallel/pp_serving.py), "
                 "and the ring attends one table with no pass to offset it",
        "spec": "a speculative draft: its shadow cache and the verify rows "
                "are addressed by the ONE table with no pass to offset it",
        "kv_quantized": "kv_dtype=int8: the scale rows are sized by "
                        "num_blocks, not by the slots' pages, and the "
                        "family's cell holds its pages to bf16 (an 8-bit "
                        "cache is not calibrated for it)",
        "vision": _DENSE_VISION,
        "transfer": "the KV transfer plane (disaggregation, evacuation): it "
                    "moves num_layers pages a block "
                    "(kvbm/layout.block_shape_for), one pass's share of "
                    "what a block of slots holds",
        "kvbm": "KVBM offload tiers: kvbm/layout.py counts num_layers pages "
                "a block hash, and the gather reads block ids below "
                "num_blocks: one pass's slots of every layer",
    },
    # stated by a module; each sentence is the whole message, and they are
    # asked in this order once the five above have refused nothing
    "window_extras": {
        "sp": "sliding-window attention (gpt-oss/gemma) does not ride the "
              "ring (sp) path yet; use chunked prefill on sp=1",
    },
    "expert_ffn": {
        "vision": "multimodal serving covers the dense family only",
    },
    "no_adapters": {
        "lora": "LoRA serving covers the llama/qwen dense family only",
    },
}


def traits(cfg) -> tuple:
    """The rows of ``_WHY`` that ``cfg`` answers for, in the table's order."""
    found = {
        "state": bool(state_spec(cfg)),
        "ring": window_ring(cfg) is not None,
        "rows": bool(getattr(cfg, "latent_rows", False)
                     or getattr(cfg, "experts_held", None)),
        "groups": len(page_groups(cfg)) > 1,
        "passes": page_passes(cfg) > 1,
        "no_adapters": not getattr(family(cfg), "ADAPTERS", False),
    }
    stated = getattr(family(cfg), "TRAITS", ())
    return tuple(t for t in _WHY if found.get(t) or t in stated)


def check_supported(cfg, *, tp=1, pp=1, sp=1, spec=False, lora=False,
                    kv_quantized=False, vision=False, transfer=False,
                    kvbm=False) -> None:
    """What ``cfg`` cannot do yet is refused here, each with its reason: at
    engine construction, and with ``transfer`` where the transfer plane is
    asked for. Raises for the first thing asked that a trait of ``cfg`` on
    the one-chip text path refuses, with every such trait's reason; then for
    what a trait its module states refuses. A latent without an indexer that
    states no layout never comes here as rows: ``place_latent`` leaves it one
    head wherever a refusal would hit."""
    asked = {
        "tp": tp > 1, "pp_sp": pp > 1 or sp > 1, "sp": sp > 1, "spec": spec,
        "lora": lora, "kv_quantized": kv_quantized, "vision": vision,
        "transfer": transfer, "kvbm": kvbm,
    }
    mine = traits(cfg)
    one_chip = [t for t in mine if t in _WHAT]
    if "groups" in one_chip and page_groups(cfg)[0][1] is not None:
        raise ValueError(
            f"{type(cfg).__name__}: the first page group lives as long as "
            "the request (the engine's own table and allocator are its)"
        )
    what = " + ".join(
        _WHAT[t].format(name=type(cfg).__name__) for t in one_chip
    )
    for key, hit in asked.items():
        whys = dict.fromkeys(
            _WHY[t][key] for t in one_chip if key in _WHY[t]
        )
        if hit and whys:
            raise ValueError(f"{what} does not run with {'; '.join(whys)}")
    for t in mine:
        if t not in _WHAT:
            for key, sentence in _WHY[t].items():
                if asked[key]:
                    raise ValueError(sentence)


def state_spec(cfg) -> tuple:
    """Per-layer arrays ONE SLOT holds beside the paged keys, as (name,
    shape, dtype): a state-space mixer's recurrent state and its
    convolution's tail (``falcon_h1.state_spec``), a linear-attention
    layer's matrix state and its tail (``solar_open2.state_spec``) or its
    matrix state alone (``minicpm_sala.state_spec``); () for
    every family whose only state is pages. engine/state_cache.py builds the
    store from it, one array a layer of ``state_layers``, and the step
    programs take and return it only where it is not empty. The family's
    module answers (its ``state_spec``), as it answers the questions below
    where it has something to say."""
    return _ask(cfg, "state_spec", ())


def page_layers(cfg) -> tuple:
    """The layers whose attention keeps PAGES of keys and values, in order:
    every layer, except where a family's layers are of kinds that keep
    different state (``solar_open2``: its softmax-attention layers only). The
    engine allocates one pair of page arrays a layer named here and
    ``attend`` finds a layer's pair by its place in this tuple
    (``layer_index``). One block table still serves every page layer. How
    many SLOTS of pages a block id names is ``page_slots``: a layer named
    here may keep more than one (``page_passes``)."""
    own = _ask(cfg, "page_layers")
    return tuple(range(cfg.num_layers)) if own is None else own


def page_passes(cfg) -> int:
    """How many pools each page layer's arrays hold, one behind another: 1,
    but for a family that runs its stack several times a token and keeps a
    cache slot a (pass, layer) (``ouro.page_passes``). Block id ``b`` of pass
    ``t`` is page ``t x num_blocks + b`` of the layer's arrays; the family's
    ``forward`` hands ``attend`` the pass (``page_pass``) and the engine's
    seams add ``t x num_blocks`` to the tables they hold, so a run of
    consecutive pages stays a run."""
    return int(_ask(cfg, "page_passes", 1))


def page_slots(cfg) -> int:
    """The slots of pages ONE block id names (what a block holds, a prefix
    hit restores and a released block gives back): a slot a page layer, times
    the passes that each keep their own (``page_passes``). It may outnumber
    the model's layers."""
    return len(page_layers(cfg)) * page_passes(cfg)


def page_groups(cfg) -> tuple:
    """The page layers in GROUPS, each ``(layers, lifetime)``: ``lifetime``
    None (a page lives as long as its request) or a window in positions (a
    page is let go once it lies wholly behind ``position - window``). A
    group has page arrays, a ``BlockAllocator`` and a table a row of its
    own; the group that lives as long as the request comes first. Every
    family answers ONE group of all its ``page_layers`` with lifetime None,
    and the engine builds for it what it always built, unless its module
    says otherwise (``cohere2_moe.page_groups``: pages by layer kind)."""
    own = _ask(cfg, "page_groups")
    return ((page_layers(cfg), None),) if own is None else own


def page_shapes(cfg) -> tuple:
    """A token's shape in each page layer's two arrays, in ``page_layers``'
    order: ``((k rows, k lanes), (v rows, v lanes))`` a layer. Every family
    answers ``(num_kv_heads, head_dim)`` for both arrays of every layer, and
    the engine allocates what it always did, unless its module says
    otherwise (``dots3_note.page_shapes``: a page GROUP's arrays take the
    shape its layers need: the latent's own rows in the first, the one tile
    a step reads in the second)."""
    own = _ask(cfg, "page_shapes")
    if own is not None:
        return own
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
    return _ask(cfg, "pooled_keys")


def state_layers(cfg) -> tuple:
    """The model layers that keep SLOT STATE (``state_spec``), in order: none
    for a family without it, every layer for Falcon-H1 (its mixer runs beside
    attention in each), the linear-attention layers for ``solar_open2``."""
    if not state_spec(cfg):
        return ()
    own = _ask(cfg, "state_layers")
    return tuple(range(cfg.num_layers)) if own is None else own


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
    return _ask(cfg, "window_ring")


def summary_spec(cfg) -> tuple:
    """The third answer beside ``page_layers`` and ``state_spec``: what ONE
    CLOSED WINDOW of a request keeps a layer, as (name, shape, dtype)
    (``evabyte.summary_spec``: a summary key and value a chunk a head); ()
    for every family without a ``window_ring``. The engine keeps them in
    summary blocks, one a window, taken when the window opens and released
    with the request (engine ``summary_allocator``)."""
    return _ask(cfg, "summary_spec", ())


def prefix_reusable(cfg) -> bool:
    """Whether a block hash restores everything a request needs of its
    prefix. Pages, yes; a recurrent state is not kept per block, so a family
    with ``state_spec`` declines prefix hits (the prompt prefills whole) and
    neither registers nor publishes its blocks as reusable. Nor does a ring:
    a closed window's pages are gone, and its summaries are not kept by
    block hash (they could be: a summary is a function of its own block)."""
    return not state_spec(cfg) and window_ring(cfg) is None


def init_params(rng, cfg):
    return family(cfg).init_params(rng, cfg)


def forward_fn(cfg, mesh=None, use_pallas: bool = False,
               interpret: bool = False):
    """Forward pass for the family: its module's ``forward``, or what its
    ``forward_fn(cfg, mesh, use_pallas, interpret)`` picks. The expert
    families pick, so serving never pays dense all-expert FLOPs (ADVICE r2):

    - experts replicated or held (no mesh / tp==1): the token-sorted grouped
      path (``moe.moe_ffn_grouped``, T*K routed rows, each touched expert
      read once); ``use_pallas`` picks its multiplication, the Pallas kernel
      ``moe_grouped_matmul`` (``interpret``: off-TPU tests) or the
      ``jax.lax.ragged_dot`` twin (``moe.grouped_forward``)
    - experts sharded over tp (EP rides the TP axis; ``moe``, ``mla``,
      ``gptoss``): each shard computes its local experts, one psum combines,
      the collective of a TP row matmul (``moe.ep_psum_shard_map``)
    """
    fam = family(cfg)
    own = getattr(fam, "forward_fn", None)
    return own(cfg, mesh, use_pallas, interpret) if own else fam.forward


def lm_logits_fn(cfg):
    return family(cfg).lm_logits


def param_specs(cfg) -> dict:
    """name -> PartitionSpec for top-level and per-layer params: the dense
    attention's megatron TP here, and what the family's ``layer_specs(cfg)``
    adds to a layer (``llama``: the dense FFN's column / row specs; an expert
    family: its stacks on the EXPERT dim over the tp axis, EP riding the same
    devices as attention TP). A family that refuses ``tp > 1`` at
    construction names only what follows the heads; the rest replicates
    (``default``)."""
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
    layer.update(_ask(cfg, "layer_specs", {}))
    return {"top": top, "layer": layer, "default": P()}


def kv_cache_spec(cfg, tp: int = 1) -> P:
    """Paged-KV sharding for the family. Caches shard kv_heads over TP when
    they divide evenly; otherwise (MQA / MLA-latent 1-head caches, or GQA
    with fewer kv heads than TP shards) the cache replicates — the layout
    real MLA deployments use, and the same condition the engine's Pallas
    eligibility check uses."""
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
    return P(None, AXIS_TP)
