"""TpuEngine: continuous-batching paged-KV serving engine on JAX/XLA.

The part the reference delegates to vLLM/SGLang/TRT-LLM — here it is
framework-native and TPU-first:

- prefill and decode are two separately-compiled XLA programs with static
  shapes (prompt lengths bucketed, decode batch fixed-width with idle slots),
  so the steady state never recompiles;
- the paged KV cache lives in HBM as [num_blocks, block_size, kv_heads,
  head_dim] per layer, sharded over the TP mesh axis on kv_heads;
- sampling is fused into both programs (only token ids [B] return to host);
- device-side prefix-cache reuse: the host BlockAllocator content-addresses
  sealed blocks by chained sequence hash, prefill feeds only the un-cached
  suffix and attends over cached pages via the block table;
- device calls run in an executor thread so the asyncio control plane (request
  plane heartbeats, event publishing) never stalls behind the TPU.

Model-parallel execution: params carry NamedShardings from
parallel/mesh.py; XLA GSPMD inserts the ICI collectives (psum after
row-parallel matmuls). One engine process per TP slice, like one reference
worker per NCCL TP group.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import math
import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial, wraps
from typing import Any, AsyncIterator, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kv_router.publisher import KvEventPublisher, WorkerMetricsPublisher
from ..llm.protocols.common import (
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_STOP,
    BackendOutput,
    PreprocessedRequest,
)
from ..models import llama, registry
from ..models import moe as moe_lib
from ..models.vision import IMAGE_TOKEN_ID
from ..ops import attention as att
from ..ops.paged_attention import GroupView, PagedAttention
from ..parallel import mesh as meshlib
from ..runtime.config import ENV_KV_BLOCK_SIZE, env_int
from ..runtime.device import device_info, hbm_bytes_per_s, on_tpu
from ..runtime.engine import Context
from ..runtime.errors import (
    ContextLengthError,
    GuidedRejectedError,
    InvalidRequestError,
)
from ..runtime.faults import FAULTS
from ..runtime.attribution import get_attribution
from ..runtime.flight_recorder import get_flight_recorder
from ..runtime.slo import get_slo_accountant, sla_t0_ns, spec_from_annotations
from ..runtime.tasks import spawn_bg
from ..runtime.logging import get_logger
from ..runtime.tracing import get_tracer
from ..tokens import TokenBlockSequence
from .allocator import BlockAllocator, OutOfBlocks, Ring, WindowGroup
from . import step_args
from .telemetry import (
    PENDING_SPANS_MAX,
    StepStats,
    launch,
    loop_span,
    now_ns,
    pending_arrivals,
    pending_launches,
    pending_request_spans,
    pending_spans,
    record_arrival,
    record_request_span,
    submit_span,
)
from .sampling import (
    TOP_LOGPROBS_K,
    SlotSampling,
    counts_need,
    first_token_epilogue,
    rows_epilogue,
)

log = get_logger("engine")


@dataclasses.dataclass
class TpuEngineConfig:
    model: llama.LlamaConfig
    num_blocks: int = 512
    # explicit values win; DTPU_KV_BLOCK_SIZE configures what callers leave open
    block_size: int = dataclasses.field(
        default_factory=lambda: env_int(ENV_KV_BLOCK_SIZE, 16)
    )
    max_batch_size: int = 8
    # max_context may exceed the largest prefill bucket: prompts prefill in
    # bounded chunks (one chunk per engine-loop tick, so running decodes
    # never starve behind a long prefill — the reference treats chunked
    # prefill as table stakes, lib/mocker/src/protocols.rs:112,
    # components/src/dynamo/trtllm/engine.py:119)
    max_context: int = 2048
    tp: int = 1
    # context parallelism: chunk prefill attention rides ring_extend_attention
    # over the sp mesh axis (parallel/ring.py) — the long-context scale path
    sp: int = 1
    # pipeline parallelism for SERVING (parallel/pp_serving.py): layer params
    # + paged KV stacked and sharded over a pp mesh axis, shard_map wavefront
    # forward. The reference forwards pipeline_parallel_size into its engines
    # (components/src/dynamo/trtllm/engine.py:118); here it is a first-class
    # engine dimension. pp>1 covers the core dense text path (no LoRA/
    # vision/sp/MoE/pallas yet).
    pp: int = 1
    prefill_buckets: Tuple[int, ...] = (64, 128, 256, 512, 1024, 2048)
    seed: int = 0
    # the Pallas attention kernels behind ops/paged_attention.py: None =
    # auto-enable on the TPU backend (_resolve_use_pallas has the rule), force
    # with True/False (tests run them via the interpreter on CPU)
    use_pallas: Optional[bool] = None
    # decode horizon: run this many decode iterations inside one XLA program
    # (lax.scan, sampled tokens fed back device-side) so per-dispatch launch
    # latency amortizes over N tokens. Stop conditions are applied host-side
    # post-hoc (at most N-1 speculatively-decoded tokens are discarded).
    # None = auto-tune from the measured device round-trip at startup
    # (the best value tracks the dispatch->readback round trip, which
    # differs by device and host — no constant fits all).
    decode_steps: Optional[int] = None
    # in-flight decode horizons: each horizon's result readback starts at
    # dispatch on the fetch pool, so with depth>=2 the device->host round
    # trip (latency, not bandwidth — concurrent fetches overlap) hides
    # behind the next horizon's compute. Each extra slot adds decode_steps
    # tokens of emission latency and speculation waste at stop. None =
    # auto-tune with decode_steps (depth 2 only when the round trip
    # outlasts two steps).
    decode_pipeline: Optional[int] = None
    # multi-LoRA serving (lora/adapters.py): N static adapter slots baked
    # into the programs at build; hot-load/unload are in-place table updates
    # with zero recompiles. 0 disables (no lora ops in the hot path).
    lora_max_adapters: int = 0
    lora_rank: int = 16
    lora_targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo")
    # pluggable logits processors (logits_processing/): STATIC (name, fn)
    # pairs traced into the programs; requests opt in by name via the
    # "logits_processors" annotation. () disables (zero hot-path cost).
    logits_processors: Tuple[Tuple[str, Any], ...] = ()
    # multimodal: vision tower config (models/vision.py). Prompts carry
    # image placeholder runs (image_token_id); prefill splices the encoded
    # patch embeddings over them (inputs_embeds path in models/llama.py).
    vision: Optional[Any] = None
    image_token_id: int = IMAGE_TOKEN_ID
    # speculative decoding (docs/speculative_decoding.md; the reference
    # exposes it through its vLLM adapter — draft-model speculation,
    # docs/features/speculative_decoding). A draft model config enables it:
    # the draft keeps a SHADOW paged KV cache addressed by the same block
    # tables as the main cache, drafts spec_k greedy tokens per round, and
    # ONE main-model forward over the k candidate positions verifies them
    # (query_len=k+1 rows of the unified ragged kernel). Greedy-equality is the
    # invariant: output is token-identical to the plain engine; the draft
    # only ever changes the acceptance rate. Eligible rows: temperature 0,
    # no penalties, no logprobs, no logits processors (mixed batches fall
    # back to the normal horizon program for the whole dispatch).
    spec_draft: Optional[llama.LlamaConfig] = None
    spec_k: int = 4
    # guided (grammar-constrained) decoding (dynamo_tpu/guided; reference
    # nvext guided_json/regex/choice + response_format). Grammars compile to
    # token-class tables applied INSIDE the decode programs; the FSM state
    # rides the horizon scan carry, so guided rows keep full pipelining.
    # 0 disables (no guided ops in the hot path). The caps bound the
    # per-slot device tables [B, states, classes]; grammars that compile
    # beyond them are rejected per request. Requires the engine to be
    # constructed with guided_vocab=(vocab byte forms, eos_id).
    guided_max_states: int = 0
    guided_max_classes: int = 320
    # mixed continuous batching (ops/pallas_unified + the mixed engine
    # step): when a prefill chunk and resident decode rows coexist, ONE
    # fused dispatch serves both — the chunk rides along with the decode
    # batch through the unified ragged paged-attention kernel instead of
    # stalling it behind a separate prefill program. None = defer to the
    # DTPU_MIXED env (default on). Auto-gated off for the paths the fused
    # program does not cover yet (pp/sp, spec decode, vision, LoRA,
    # multihost, windowed/softcapped families) — those fall back to the
    # split prefill/decode dispatches unchanged.
    mixed_admission: Optional[bool] = None
    # paged-KV storage precision (ops/quant.py; docs/operations.md "KV
    # precision"). "auto" defers to DTPU_KV_DTYPE (default "model" — exactly
    # today's behavior); "int8" stores the cache as int8 with per-block-per-
    # kv-head f32 scales, halving KV bytes in HBM, on the transfer wire and
    # in the KVBM tiers vs bf16 (quartering vs f32) and doubling effective
    # KV capacity per block budget, at a bounded quantization error
    # (amax/254 per element). The draft model's shadow cache stays in model
    # dtype — it is small and its values only steer acceptance, never output.
    kv_dtype: str = "auto"
    # pages by layer kind (models/registry.page_groups with a windowed
    # group): the pages of a windowed group's pool. None = the engine's rule
    # (``window_pool_pages``): a row's table and one more window a row.
    # ``num_blocks`` stays the pages of the group that lives as long as the
    # request.
    window_blocks: Optional[int] = None
    # the single-step ``decode`` (what the loop falls back to while a request
    # waits for a slot or for pages) is run over no row when the engine is
    # built, so its compile, or its load from the compile cache, is paid
    # then and not at the first tick that finds a request waiting, with
    # every resident row stalled behind it (TpuEngine._ready_single_step)
    ready_single_step: bool = False

    def __post_init__(self):
        bad = [b for b in self.prefill_buckets if b % self.block_size]
        if bad:
            raise ValueError(
                f"prefill_buckets {bad} not multiples of block_size {self.block_size}"
            )
        from ..ops.quant import resolve_kv_dtype

        self.kv_dtype = resolve_kv_dtype(self.kv_dtype)

    @property
    def kv_quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def prefill_chunk(self) -> int:
        """Largest single prefill dispatch; longer prompts chunk at this."""
        return self.prefill_buckets[-1]

    @property
    def ring(self) -> Optional[Ring]:
        """The family's ring of pages (models/registry.window_ring), or None:
        a page lives as long as its request."""
        positions = registry.window_ring(self.model)
        if positions is None:
            return None
        return Ring(positions, self.block_size, self.max_context)

    @property
    def max_blocks_per_seq(self) -> int:
        """Entries of a row's block table: a page a ``block_size`` tokens of
        ``max_context``; under a ring, the ring's pages and then a summary
        block a window."""
        ring = self.ring
        if ring is not None:
            return ring.table_width
        return (self.max_context + self.block_size - 1) // self.block_size

    def window_table_pages(self, window: int) -> int:
        """Entries of a windowed group's run in a row's table: the window, and
        ahead of it the largest prefill bucket or what the decode horizons in
        flight book (whichever is longer), and one page (a window does not
        start on a page's edge)."""
        ahead = max(
            self.prefill_chunk,
            (self.decode_steps or 1) * (self.decode_pipeline or 1) + 2,
        )
        return -(-(window + ahead) // self.block_size) + 1

    def window_pool_pages(self, window: int) -> int:
        """THE rule that sizes a windowed group's pool from what a
        deployment states already (rows, window, buckets): every row's table
        full, and one window more a row (the tail of a cached prefix that the
        row's next request comes back to while the last one's pages are still
        cached), and the scratch page. ``window_blocks`` overrides it. What a
        page costs is the GROUP's: ``block_size`` tokens of its layers' own
        shape (registry.page_shapes), summed over its layers, not
        ``num_blocks``' bytes a page: dots3-note's sliding group is 3 layers x
        (8 + 2) rows x 256 B = 7.5 KiB a token, 120 KiB a 16-token page,
        where a page of its full group is 2 layers x (4 + 2) rows = 48 KiB."""
        if self.window_blocks is not None:
            return int(self.window_blocks)
        per_row = self.window_table_pages(window) + -(-window // self.block_size)
        return 1 + self.max_batch_size * per_row


def _model_param_bytes(mcfg) -> int:
    """Parameter bytes a decode step reads at small batch: the per-step HBM
    traffic floor. Counted from the shapes of the family's OWN parameter
    pytree (``registry.init_params`` in the abstract: nothing is drawn), so
    a new family brings no arithmetic here. An expert stack (a leaf the
    family names, ``registry.expert_stack_leaves``: stacked over the experts
    the chip holds) counts its top-k experts only: traffic, not capacity."""
    shapes = jax.eval_shape(
        lambda k: registry.init_params(k, mcfg), jax.random.PRNGKey(0)
    )
    stacks = registry.expert_stack_leaves(mcfg)
    top_k = getattr(mcfg, "num_experts_per_tok", 0)

    def leaf_bytes(name, x):
        n = math.prod(x.shape) * x.dtype.itemsize
        if name not in stacks:
            return n
        return n * min(top_k, x.shape[0]) // x.shape[0]

    total = sum(
        leaf_bytes(name, x) for name, x in shapes.items() if name != "layers"
    )
    # a stack run several times a token (a slot a (pass, layer)) reads its
    # layers once a pass: a layer does not stay on chip between them
    passes = registry.page_passes(mcfg)
    for layer in shapes["layers"]:
        total += passes * sum(leaf_bytes(name, x) for name, x in layer.items())
    return int(total)


def measure_device_rtt(device, tries: int = 3) -> float:
    """Median dispatch->readback round-trip for a trivial op. np.asarray (a
    real fetch), not block_until_ready: the round trip the engine loop pays
    per horizon is dispatch + device->host copy of the packed results, and
    only a fetch includes the copy. (chip_smoke.py prints whether
    block_until_ready itself waits for the device.)"""
    x = jax.device_put(jnp.zeros((8,), jnp.float32), device)
    np.asarray(x + 1)  # warm the op cache  # dtpu: ignore[HOST-SYNC] — deliberate: this IS the RTT probe
    samples = []
    for _ in range(tries):
        t0 = time.perf_counter()
        np.asarray(x + 1)  # dtpu: ignore[HOST-SYNC] — deliberate fetch: measuring the round-trip is the point
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def autotune_decode_schedule(mcfg, device) -> Tuple[int, int]:
    """(decode_steps, decode_pipeline) from measured RTT + a roofline
    per-step estimate.

    Model: a horizon must keep the device busy for >= ~1 RTT so that with
    pipeline depth 2 the readback of horizon N hides behind horizon N+1's
    compute. steps ~ 0.45 * RTT / t_step rounded to a power of two, floor
    8, cap 64 — longer horizons waste speculative tokens at stop. t_step is
    the pure-weights roofline (parameter bytes over the device's published
    HBM bandwidth, runtime/device.py); 0.45 was fitted on earlier chip
    runs, since deleted, and has not been re-fitted on today's code
    (ROADMAP D5). Low-RTT devices keep short horizons (less speculation
    waste, lower emission latency) and skip pipelining. An unknown device
    kind or a failed probe raises: there is no schedule to assume for a
    device that does not answer."""
    t_step = max(_model_param_bytes(mcfg) / hbm_bytes_per_s(device), 1e-4)
    rtt = measure_device_rtt(device)
    ratio = 0.45 * rtt / t_step
    steps = 8
    while steps < 64 and steps < ratio:
        steps *= 2
    pipeline = 2 if rtt > 2 * t_step else 1
    log.info(
        "decode schedule auto-tuned: rtt=%.1fms t_step~%.2fms -> steps=%d pipeline=%d",
        rtt * 1e3, t_step * 1e3, steps, pipeline,
    )
    return steps, pipeline


@dataclasses.dataclass
class _Seq:
    req: PreprocessedRequest
    context: Context
    out_queue: asyncio.Queue
    seq: TokenBlockSequence               # prompt + generated
    slot: int = -1
    block_ids: List[int] = dataclasses.field(default_factory=list)
    # a family with a ring: the summary blocks of the windows it has opened
    summary_ids: List[int] = dataclasses.field(default_factory=list)
    # pages by layer kind: for each windowed group the run of pages held,
    # ``win_ids[g]`` the pages of page indexes ``win_first[g] ..``
    win_first: List[int] = dataclasses.field(default_factory=list)
    win_ids: List[List[int]] = dataclasses.field(default_factory=list)
    # run_chunks[w]: of the first ``w`` whole chunks of ``block_ids`` (the
    # decode kernel's chunks of pages), those that are consecutive block ids;
    # grown as chunks fill (engine ``_count_paged``)
    run_chunks: List[int] = dataclasses.field(default_factory=lambda: [0])
    produced: int = 0
    last_token: int = 0
    cached_tokens: int = 0
    prefill_pos: int = 0                  # prompt tokens whose KV is written
    commit_upto: int = 0                  # prompt blocks content-addressed so far
    prefilled: bool = False               # prefill complete -> decode eligible
    # final chunk dispatched, first-token readback in flight (the loop must
    # neither prefill this sequence again nor decode it yet)
    prefill_inflight: bool = False
    # this request keeps output_counts maintained (penalties or an opted-in
    # logits processor) — batchmates' rows accumulate too and must be reset
    # before reuse
    counting: bool = False
    # multimodal: per-prompt-position soft-token override (image spans).
    # mm_embeds [prompt_len, H] model-dtype, mm_mask [prompt_len] bool.
    # Placeholder ids hash identically for different images, so mm requests
    # opt out of the content-addressed prefix cache entirely (no_cache).
    mm_embeds: Optional[np.ndarray] = None
    mm_mask: Optional[np.ndarray] = None
    no_cache: bool = False
    # speculative decoding: prompt positions whose DRAFT KV is written.
    # Independent of prefill_pos — the draft re-prefills from token ids even
    # over regions whose MAIN KV arrived by prefix-cache hit or disagg/kvbm
    # import, so draft coverage of the whole prompt is an invariant.
    draft_prefill_pos: int = 0
    # guided decoding: compiled token tables + current FSM state (host view;
    # the device copy rides the horizon carry and resyncs from this on every
    # chain break)
    guided_tables: Optional[Any] = None
    guided_state: int = 0
    # speculative decoding: this request can ride spec rounds (greedy, no
    # penalties/logprobs/processors/guidance — the same per-request-static
    # predicate _spec_eligible applies batch-wide). Ineligible requests skip
    # draft prefill: their draft KV would never be read.
    spec_ok: bool = True
    done: bool = False
    # lifecycle milestones (0 = not reached) on time.monotonic_ns(), the
    # clock of the loop's spans (telemetry.now_ns): stamped host-side by the
    # loop / accept path, turned into engine.queue / engine.prefill /
    # engine.decode spans and the SLO ledger's intervals when the request
    # finishes; TpuEngine._unix_ns moves one onto the wall clock
    t_queued: int = 0
    t_admitted: int = 0
    t_prefill_start: int = 0
    t_first_token: int = 0
    # SLO accounting (runtime/slo.py): the request's promise parsed from the
    # sla annotation at accept time; None = unclassified (no accounting)
    sla: Optional[Any] = None


@dataclasses.dataclass
class _Chain:
    """An in-flight decode dispatch, a link of the chain: results not yet
    fetched (a horizon's packed [N, B, 2+2K]; a mixed step's token and
    logprob arrays), the device-side carry for dispatching the next link
    without a host round-trip, and the per-slot sequence snapshot taken at
    dispatch time (results must never be applied to a sequence admitted into
    a recycled slot afterwards)."""
    packed: Any
    tokens: jax.Array
    seq_lens: jax.Array
    steps: jax.Array
    seqs: List[Optional["_Seq"]] = dataclasses.field(default_factory=list)
    # fetch future (np.asarray on the fetch pool): started at dispatch so
    # pipelined horizons' device->host RTTs overlap instead of serializing
    fetch: Any = None
    # None => normal horizon ([N, B, 2+2K]); k => speculative horizon
    # ([rounds, B, 1+2k]: advance count + k candidate tokens + k logprobs).
    # The device carry (tokens/seq_lens/steps) means the same thing either
    # way, so spec and normal horizons chain on each other freely.
    spec_k: Optional[int] = None
    # guided decoding: device-resident FSM states after this horizon (chained
    # dispatches carry it forward without a host round-trip)
    g_state: Optional[jax.Array] = None
    # tokens a row advances before the host has read them: what a dispatch
    # on top of this one books beyond its own (a horizon's decode_steps)
    length: int = 0
    # its launch's place in the launch ledger (telemetry.launch): whoever
    # reads its results stamps their arrival under it
    seq: int = -1
    # a mixed step (one token a decode row beside a prefill chunk) and what
    # its StepStats needs once it is read: the chunk's tokens, whether the
    # link before it was unread at its launch, when its call began (on the
    # spans' clock, ``now_ns``), whether its chunk came prebuilt, the
    # placements its dispatch made; ``results`` where it was read at once
    # (the executor's ``sync``), else ``fetch`` resolves to them
    mixed: bool = False
    chunk_tokens: int = 0
    chained: bool = False
    t0_ns: int = 0
    prep_hit: Optional[bool] = None
    placed: int = 0
    results: Any = None


class TpuEngine:
    """AsyncEngine serving PreprocessedRequests with a real JAX model."""

    def __init__(
        self,
        config: TpuEngineConfig,
        params: Optional[llama.Params] = None,
        draft_params: Optional[llama.Params] = None,
        guided_vocab: Optional[Tuple[List[Optional[bytes]], int]] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        kv_publisher: Optional[KvEventPublisher] = None,
        metrics_publisher: Optional[WorkerMetricsPublisher] = None,
        kvbm=None,
        multihost=None,
        mh_ns: str = "",
    ):
        self.cfg = config
        self.mcfg = config.model
        # int8 paged KV (config.kv_dtype / DTPU_KV_DTYPE; ops/quant.py):
        # every cache-touching path below branches on this ONE flag
        self.kv_quantized = config.kv_quantized
        # the parallelism is known here: a latent without an indexer is held
        # as rows of 128 lanes (the kernel path) on the one-chip text path,
        # as one head everywhere else (models/registry.place_latent); what a
        # latent in rows cannot do yet is refused further down
        asked = dict(
            tp=config.tp, pp=config.pp, sp=config.sp,
            spec=config.spec_draft is not None,
            lora=config.lora_max_adapters > 0,
            kv_quantized=self.kv_quantized, vision=config.vision is not None,
        )
        self.mcfg = config.model = registry.place_latent(self.mcfg, **asked)
        if self.kv_quantized:
            if config.pp > 1:
                raise ValueError(
                    "kv_dtype=int8 does not cover pp serving yet (the pp "
                    "wavefront stacks per-layer caches without the "
                    "quantize-on-write ops); use tp/sp or kv_dtype=model"
                )
            if multihost is not None:
                raise ValueError(
                    "kv_dtype=int8 does not cover multihost groups yet (the "
                    "replay table's kv gather/scatter state wiring moves "
                    "raw arrays); use kv_dtype=model"
                )
        # namespace on the multihost dispatch channel: dp ranks / disagg
        # roles sharing one group each get their own replay table
        self._mh_ns = mh_ns
        # multi-process execution (runtime/multihost.py): process 0 runs this
        # engine normally but broadcasts every jit dispatch; followers hold
        # their own handles of the same globally-sharded arrays and replay.
        # v1 covers the core text serving path — the side paths that touch
        # device state outside the registered ops are gated off.
        self._mh = multihost
        if multihost is not None:
            if config.lora_max_adapters > 0:
                raise ValueError("multihost serving does not cover LoRA yet")
            if config.vision is not None:
                raise ValueError("multihost serving does not cover vision yet")
            if config.ready_single_step:
                raise ValueError(
                    "multihost serving does not cover ready_single_step (a "
                    "step at construction, before the followers replay)"
                )
            if kvbm is not None:
                raise ValueError("multihost serving does not cover kvbm tiers yet")
        if config.pp > 1:
            from ..parallel import pp_serving

            # family gate before any param placement, shared with
            # pp_serving._check_cfg so the operator-facing message lives
            # in one place
            registry.check_pp_supported(self.mcfg)
            if (config.lora_max_adapters or config.vision is not None
                    or config.sp > 1 or kvbm is not None
                    or config.logits_processors
                    or config.use_pallas):
                raise ValueError(
                    "pp serving covers the core dense text path (no LoRA/"
                    "vision/sp/kvbm/logits-processors/pallas yet)"
                )
            if mesh is None:
                mesh = pp_serving.make_pp_mesh(pp=config.pp, tp=config.tp)
            self.mesh = mesh
        else:
            self.mesh = mesh if mesh is not None else meshlib.make_mesh(tp=config.tp)
        # resolve the decode schedule before any program is built (both
        # knobs are baked into the compiled horizon program)
        if config.decode_steps is None or config.decode_pipeline is None:
            # probe a LOCAL device (multihost meshes span processes; RTT to
            # any local chip is representative)
            local = next(
                (d for d in self.mesh.devices.flat
                 if d.process_index == jax.process_index()),
                jax.local_devices()[0],
            )
            steps, pipeline = autotune_decode_schedule(self.mcfg, local)
            if config.decode_steps is None:
                config.decode_steps = steps
            if config.decode_pipeline is None:
                config.decode_pipeline = pipeline
        if config.spec_draft is not None:
            if config.pp > 1 or config.sp > 1:
                raise ValueError(
                    "speculative decoding covers the non-pp, non-sp engine"
                )
            if config.vision is not None or config.lora_max_adapters > 0:
                raise ValueError(
                    "speculative decoding covers the text path (no vision/"
                    "LoRA yet)"
                )
            if config.spec_draft.vocab_size != config.model.vocab_size:
                raise ValueError(
                    "draft and main model must share a vocabulary"
                    f" ({config.spec_draft.vocab_size} != "
                    f"{config.model.vocab_size})"
                )
            # a spec horizon advances at most rounds*k <= decode_steps
            # tokens, so _prepare_horizon's block booking (decode_steps per
            # horizon) covers it; k beyond the horizon budget can't be used
            config.spec_k = max(1, min(config.spec_k, config.decode_steps))
        self.guided_enabled = config.guided_max_states > 0
        if self.guided_enabled:
            if config.pp > 1:
                raise ValueError(
                    "guided decoding covers the non-pp engine (not tested "
                    "under pp)"
                )
            if guided_vocab is None:
                raise ValueError(
                    "guided decoding needs guided_vocab=(vocab byte forms, "
                    "eos_id) — see guided.vocab_bytes_from_tokenizer"
                )
        registry.check_supported(self.mcfg, **asked, kvbm=kvbm is not None)
        # pages that live one window (allocator.Ring) and summary blocks by
        # window beside them, where the family says so; None for every other
        self._ring = config.ring
        if self._ring is not None:
            chunk = self.mcfg.chunk_size
            if chunk != config.block_size:
                raise ValueError(
                    f"a page is a chunk: block_size {config.block_size} != "
                    f"the family's chunk_size {chunk}"
                )
            if any(self._ring.positions % b for b in config.prefill_buckets):
                # a prefill chunk starts at a multiple of the largest bucket
                # and is at most that long: it never straddles a window
                raise ValueError(
                    f"prefill_buckets {config.prefill_buckets} do not divide "
                    f"the window of {self._ring.positions} positions: a "
                    "chunk would straddle two windows"
                )
        pooled = registry.pooled_keys(self.mcfg)
        if pooled is not None and pooled.stride != config.block_size:
            raise ValueError(
                f"a pooled key's stride is the page: block_size "
                f"{config.block_size} != the family's kernel_stride "
                f"{pooled.stride}"
            )
        # whether the Pallas kernels are active for this engine (one
        # resolution shared by _build_programs and the mixed gate below),
        # and whether they run compiled (TPU backend) or in the Pallas
        # interpreter (a forced use_pallas=True off-TPU: the CPU tests)
        self.use_pallas = self._resolve_use_pallas()
        self.kernels_interpreted = self.use_pallas and not on_tpu()
        if self.kernels_interpreted:
            log.warning(
                "use_pallas forced on backend %r: every Pallas kernel runs "
                "in the interpreter, not compiled", jax.default_backend(),
            )
        if self.kv_quantized and self.use_pallas and on_tpu():
            raise ValueError(
                "kv_dtype=int8 with the Pallas kernels does not compile for "
                "the TPU backend: the kernels DMA each page's [kv_heads] f32 "
                "scale row, a slice Mosaic refuses (minor dim not aligned to "
                "the 128-lane tiling). Use kv_dtype=model, or use_pallas="
                "False for the pure-JAX int8 path."
            )
        # mixed continuous batching: a prefill chunk fuses into the decode
        # batch through ONE program (unified ragged paged attention). The
        # knob gates intent; the feature additionally requires the Pallas
        # kernels by default — on a pure-JAX engine the fused step would
        # run the O(R*Tq*T) reference attention, slower than the split
        # dispatches it replaces, so only an EXPLICIT mixed_admission=True
        # (--mixed on; CPU/interpret tests) forces it.
        #
        # MIXED GATE (the one documented exclusion site — tools/analysis
        # MIXED-GATE pins it; add a family here only with a baseline
        # entry). Remaining exclusions and why:
        #   pp/sp    — the fused step covers neither the wavefront nor the
        #              ring forward;
        #   vision   — per-chunk soft-token splicing is not threaded
        #              through the packed buffer yet;
        #   multihost — the fused program is not in the replay table.
        # Spec decode, LoRA and the windowed/sink/softcap families
        # (gpt-oss/gemma) ARE mixed-eligible: verify rides the unified
        # kernel as q_len=k+1 rows, per-row adapter ids thread through the
        # packed buffer, and window/sink/softcap are per-row kernel
        # attributes.
        mixed = config.mixed_admission
        if mixed is None:
            mixed = os.environ.get("DTPU_MIXED", "1").lower() not in (
                "0", "", "false", "off"
            )
        self.mixed_enabled = bool(
            mixed
            and (config.mixed_admission is True or self.use_pallas)
            and config.pp == 1
            and config.sp == 1
            and config.vision is None
            and multihost is None
        )
        log.info(
            "attention path on backend %r: use_pallas=%s (config %s), "
            "mixed_enabled=%s, kernels %s",
            jax.default_backend(), self.use_pallas, config.use_pallas,
            self.mixed_enabled,
            "interpreted" if self.kernels_interpreted else "compiled",
        )
        self.kv_publisher = kv_publisher
        self.metrics_publisher = metrics_publisher
        self.allocator = BlockAllocator(config.num_blocks, config.block_size)
        # the store of summary blocks: every window of as many requests at
        # ``max_context`` as the page pool holds whole rings (block 0 is its
        # scratch); its blocks are pages of the pool's arrays above
        # ``num_blocks`` (_init_caches, ops/attention.py)
        self.summary_allocator = None
        if self._ring is not None:
            rings = max((config.num_blocks - 1) // self._ring.pages, 1)
            self.summary_allocator = BlockAllocator(
                1 + rings * self._ring.windows, self._ring.pages
            )
        # pages by layer kind (registry.page_groups): the first group's pages
        # live as long as the request and are ``allocator``'s, as every other
        # family's; each further group has a pool, an allocator and a run of
        # a row's table of its own (allocator.WindowGroup). Empty for the
        # families that answer one group
        self._win_groups: List[WindowGroup] = []
        col = config.max_blocks_per_seq
        for layers, window in registry.page_groups(self.mcfg)[1:]:
            pages = config.window_table_pages(window)
            self._win_groups.append(WindowGroup(
                tuple(layers), int(window), config.block_size, pages, col,
                BlockAllocator(
                    config.window_pool_pages(window), config.block_size,
                    keep_hits=True,
                ),
            ))
            col += pages + 1
        self._host_rng = np.random.default_rng(config.seed)
        # multi-tier KV (kvbm/pool.py): sealed blocks write through to host
        # DRAM (G2) / disk (G3); admission onboards matched prefixes back
        self.kvbm = kvbm
        # fleet-wide KV reuse (kvbm/directory.py): serving glue attaches a
        # GlobalKvDirectory so tier offloads/evictions advertise/withdraw
        # on the shared directory plane (maintained in _publish_events)
        self.kv_directory = None
        # (block_id, seq_hash, priority): 0 = prompt-prefix blocks (highest
        # reuse odds -> offload first), 1 = decode-sealed blocks; the kvbm
        # priority queue transfers in that order (kvbm/pool.py OffloadQueue,
        # reference offload.rs:10-16)
        self._offload_pending: List[Tuple[int, int, int]] = []

        # --- place params + caches on the mesh ---
        self._forward = (
            None if config.pp > 1 else registry.forward_fn(
                self.mcfg, self.mesh, use_pallas=self.use_pallas,
                interpret=self.kernels_interpreted,
            )
        )
        # the one-chip grouped expert path reports its routing each step
        # (StepStats.moe_*): three numbers riding the readback a decode or
        # mixed step already makes. None where no step carried them.
        # A latent held as rows adds what its decode rows read, under the
        # names of StepStats' fields (mla.read_counters: dsa_* or mla_*).
        self._read_counters = registry.read_counters(self.mcfg)
        self._moe_counted = (
            (registry.counts_routing(self.mcfg) or bool(self._read_counters))
            and config.pp == 1 and meshlib.tp_size(self.mesh) == 1
        )
        self._moe_last: Optional[Tuple[int, ...]] = None
        self._lm_logits = registry.lm_logits_fn(self.mcfg)
        with self.mesh:
            if params is None and (
                config.pp > 1 or multihost is not None or self._eplb_enabled
                or self.mesh.devices.size == 1
            ):
                # these placements start from whole weights on the default
                # device (pp stacking, per-process uploads, EPLB slot
                # expansion) — and on a one-device mesh there is no shard
                # to be born into
                params = registry.init_params(
                    jax.random.PRNGKey(config.seed), self.mcfg
                )
            if config.pp > 1:
                from ..parallel import pp_serving

                self.params = pp_serving.place_serving_params(self.mesh, params)
                k, v = pp_serving.init_pp_caches(
                    self.mesh, self.mcfg.num_layers, config.num_blocks,
                    config.block_size, self.mcfg.num_kv_heads,
                    self.mcfg.head_dim, self.mcfg.dtype,
                )
                # ONE stacked array per list: donation, multihost state
                # wiring and the decode_multi scan carry are unchanged
                self.k_caches, self.v_caches = [k], [v]
            else:
                if self._eplb_enabled:
                    # EPLB: checkpoint/warm-loaded params carry LOGICAL
                    # expert stacks; expand to physical slots + seed the
                    # remap tables before sharding (models/moe.py). The
                    # physical count must divide over the EP shards.
                    from ..models import moe as moe_mod

                    tp_n = meshlib.tp_size(self.mesh)
                    if self.mcfg.num_physical_experts % tp_n:
                        raise ValueError(
                            f"num_experts + redundant_experts = "
                            f"{self.mcfg.num_physical_experts} must divide "
                            f"over tp={tp_n} for EP sharding"
                        )
                    for lp in params["layers"]:
                        moe_mod.ensure_eplb_layer(lp, self.mcfg)
                self.params = (
                    self._shard_params(params) if params is not None
                    else self._init_params_sharded(config.seed, self.mcfg)
                )
                self.k_caches, self.v_caches = self._init_caches()
        # the second kind of state (engine/state_cache.py): what a family
        # keeps a SLOT beside its pages; None for every family whose only
        # state is pages, whose programs then neither take nor return it
        self.state = None
        spec = registry.state_spec(self.mcfg)
        if spec:
            from .state_cache import SlotState

            with self.mesh:
                # one array a name a layer that KEEPS slot state
                self.state = SlotState(
                    spec, len(registry.state_layers(self.mcfg)),
                    config.max_batch_size, NamedSharding(self.mesh, P()),
                )
        # the recurrence's counts since the last StepStats (_count_state),
        # reported under the family's prefix (ssm_* / kda_*)
        self._state_counts = [0, 0, 0]
        self._state_prefix = registry.state_prefix(self.mcfg)
        self._prefix_reusable = registry.prefix_reusable(self.mcfg)

        # --- speculative decoding: draft model + shadow paged cache ---
        # The draft cache mirrors the main cache's block geometry and is
        # addressed by the SAME block tables: content-addressed sharing is
        # safe (same block id => same token content => same draft KV, so
        # concurrent writes are idempotent), and block lifecycle needs no
        # second allocator.
        self.draft_params = None
        self.draft_k_caches = self.draft_v_caches = None
        self._spec_rounds = 0
        if config.spec_draft is not None:
            dcfg = config.spec_draft
            self._draft_forward = registry.forward_fn(dcfg, self.mesh)
            self._draft_logits = registry.lm_logits_fn(dcfg)
            self._spec_rounds = max(1, config.decode_steps // config.spec_k)
            with self.mesh:
                if draft_params is None and (
                    multihost is not None or self.mesh.devices.size == 1
                ):
                    draft_params = registry.init_params(
                        jax.random.PRNGKey(config.seed + 2), dcfg
                    )
                self.draft_params = (
                    self._shard_params(draft_params, dcfg)
                    if draft_params is not None
                    else self._init_params_sharded(config.seed + 2, dcfg)
                )
                # the draft's shadow cache stays in model dtype even under
                # kv_dtype=int8: it is spec_k-steps small, and its values
                # only move the acceptance rate, never the emitted tokens
                self.draft_k_caches, self.draft_v_caches = self._init_caches(
                    dcfg, quantized=False
                )
        # acceptance telemetry (reference reports spec acceptance through
        # its engine metrics). rounds = per-ROW rounds applied (a horizon
        # with A active rows and R rounds adds A*R); emitted = tokens
        # advanced on device, BEFORE host-side stop truncation (the
        # discarded tail past a finish is included). acceptance rate =
        # emitted / (rounds * k), in (0, 1]; a perfect draft measures 1.0.
        self.spec_stats = {"rounds": 0, "emitted": 0, "k": config.spec_k}

        # --- slot state (decode batch is fixed-width) ---
        B = config.max_batch_size
        self._slots: List[Optional[_Seq]] = [None] * B
        self._tokens = np.zeros(B, np.int32)
        self._seq_lens = np.zeros(B, np.int32)
        # entries of a row's table (a ring's pages and its summary blocks,
        # or a page a block_size of max_context): asked of the family once
        self._table_width = config.max_blocks_per_seq + sum(
            g.pages + 1 for g in self._win_groups
        )
        self._block_tables = np.zeros((B, self._table_width), np.int32)
        self._temps = np.zeros(B, np.float32)
        self._top_ks = np.zeros(B, np.int32)
        self._top_ps = np.ones(B, np.float32)
        self._min_ps = np.zeros(B, np.float32)
        self._pres = np.zeros(B, np.float32)
        self._freqs = np.zeros(B, np.float32)
        self._reps = np.ones(B, np.float32)
        self._lp_ns = np.zeros(B, np.int32)    # requested top-logprobs per slot
        # tokens a slot's request asked for: a horizon of a family with slot
        # state stops a row's recurrence there (decode_multi, max_new)
        self._max_new = np.full(B, np.iinfo(np.int32).max, np.int32)
        self._lora_slots = np.zeros(B, np.int32)  # adapter slot per batch slot
        self._lp_masks = np.zeros(
            (B, max(1, len(config.logits_processors))), bool
        )  # per-slot logits-processor opt-ins
        self._seeds = np.zeros(B, np.uint32)
        # penalty state tables (device-resident; see engine/sampling.py)
        V = self.mcfg.vocab_size
        # device_put of HOST zeros with an explicit (replicated) sharding:
        # in multi-controller JAX a committed single-device array cannot seed
        # a mesh-spanning program, while an addressable-shard put works on
        # every process; on a single-device mesh this is identical to
        # jnp.zeros. XLA resharding on the first program call applies to both
        # paths equally.
        repl = NamedSharding(self.mesh, P())
        self.output_counts = jax.device_put(np.zeros((B, V), np.int32), repl)
        self.prompt_masks = jax.device_put(np.zeros((B, V), np.int8), repl)
        self._slot_dirty = np.zeros(B, bool)   # slot's penalty tables need reset

        # --- guided decoding slot state ---
        # Per-slot compressed automaton tables (guided/tokens.py): class map
        # [B, V] + transitions [B, S, C], uploaded as one versioned unit (the
        # tables only change on admission/release, never per step).
        if self.guided_enabled:
            S_cap, C_cap = config.guided_max_states, config.guided_max_classes
            self._g_vocab, self._g_eos = guided_vocab
            self._g_active = np.zeros(B, bool)
            self._g_state = np.zeros(B, np.int32)
            self._g_class = np.zeros((B, V), np.int32)
            self._g_trans = np.full((B, S_cap, C_cap), -1, np.int32)
            # upload bookkeeping: the [B] active mask changes on every
            # guided admission AND release (cheap re-upload, own version);
            # the big [B, V] / [B, S_cap, C_cap] tables change only when a
            # guided request is ADMITTED, and then only one slot's rows —
            # tracked per slot so _guided_dev scatters rows into the device
            # copies instead of re-uploading the whole unit
            self._g_active_version = 0
            self._g_dirty_slots: set = set()
            self._g_cache: Dict[Any, Any] = {}  # grammar key -> TokenTables
            if multihost is not None:
                # multihost: the device tables are REPLAY STATE (followers
                # hold their own handles, updated by the guided_active /
                # guided_row ops) — seed identical collective arrays on
                # every process, like output_counts above
                grepl = NamedSharding(self.mesh, P())
                self._g_dev_active = jax.device_put(
                    self._g_active.copy(), grepl
                )
                self._g_dev_class = jax.device_put(
                    self._g_class.copy(), grepl
                )
                self._g_dev_trans = jax.device_put(
                    self._g_trans.copy(), grepl
                )

        self._waiting: List[_Seq] = []
        self._prefill_rr = 0  # round-robin cursor over prefilling sequences
        # chained decode: FIFO of in-flight links (results + device carry).
        # Horizons' results are fetched decode_pipeline-1 horizons behind
        # the dispatch front so readback RTT hides behind device compute; a
        # mixed step is a link too, read behind the launch of the ONE
        # program that follows it, whatever decode_pipeline says (_loop)
        self._chains: "deque[_Chain]" = deque()
        # what a mixed step with no unread link before it takes for a carry:
        # placed once, as a step program's results are (replicated over the
        # mesh, committed), so that it and a link's tokens reach the same
        # compiled program
        self._no_carry = (
            jax.device_put(
                np.zeros(config.max_batch_size, np.int32),
                NamedSharding(self.mesh, P()),
            )
            if self.mixed_enabled else None
        )
        # device-resident copies of per-slot state, name -> (device array,
        # the host snapshot it was placed from), placed again only when the
        # host copy changes (_dev); the guided tables keep their versioned
        # entries ("g/...") here too. _h2d_placements counts the host-to-
        # device placements since the last StepStats (_dev misses and the
        # host values _upload hands a jitted call)
        self._dev_cache: Dict[str, Any] = {}
        self._h2d_placements = 0
        self._loop_task: Optional[asyncio.Task] = None
        self._prefill_tasks: set = set()  # in-flight first-token readbacks
        self._last_published_load: Tuple[int, int, int] = (-1, -1, -1)
        self._wake = asyncio.Event()
        # engine health: False after a step-loop crash (watchdog deregisters
        # the worker; reference components/src/dynamo/vllm/engine_monitor.py)
        self.healthy = True
        self.on_crash: Optional[Any] = None  # callback(exc) scheduled on loop crash
        # step telemetry (engine/telemetry.py): callable(StepStats) invoked
        # after every prefill chunk / consumed decode horizon; None = off.
        # Workers wire EngineTelemetry.on_step; bench.py wires a collector.
        self.stats_hook: Optional[Any] = None
        # pending spans and admission waits since the last StepStats
        # (engine/telemetry.py loop_span): filled only while stats_hook is
        # set, bounded, carried away by _step_stats. The loop's own, and
        # those with a request for a subject (``submit``, ``deliver``)
        self._host_spans = pending_spans()
        self._request_spans = pending_request_spans()
        # the launch ledger (telemetry.launch / record_arrival): the pending
        # records, the counter that numbers every jitted call the loop
        # makes, and the newest launch whose results the loop has taken
        self._launches = pending_launches()
        self._arrivals = pending_arrivals()
        self._launch_seq = itertools.count()
        self._read_seq = -1
        self._admit_waits: deque = deque(maxlen=PENDING_SPANS_MAX)
        # wall clock less the loop's monotonic one, taken as the loop starts
        self._wall_offset_ns = 0
        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="tpu-step")
        # result readback pool: each in-flight horizon's packed fetch runs on
        # its own thread; a fetch waits for its horizon's compute (latency,
        # not bandwidth), so concurrent fetches pipeline and the loop
        # consumes at device cadence
        self._fetch_executor = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="tpu-fetch"
        )
        self._offload_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpu-offload"
        )
        # async host step-prep (engine/prep.py, DTPU_ASYNC_PREP): step N+1's
        # chunk packing + upload run on a prep thread under step N's device
        # compute. Multihost keeps serial prep (dispatch args are part of
        # the leader's replay-ordered broadcast).
        from .prep import ChunkPrep, async_prep_enabled

        self._prep = None
        if async_prep_enabled() and multihost is None:
            self._prep = ChunkPrep(self._chunk_arrays, upload=jnp.asarray)
        # multimodal vision tower (models/vision.py) + encoder cache
        self.vision_params = None
        self._encode_image_fn = None
        self.encoder_cache = None
        if config.vision is not None:
            from ..llm.encoder_cache import EncoderCacheManager
            from ..models import vision as vis

            if config.vision.out_hidden_size != self.mcfg.hidden_size:
                raise ValueError(
                    "vision.out_hidden_size must match the language model "
                    f"hidden size ({self.mcfg.hidden_size})"
                )
            with self.mesh:
                self.vision_params = vis.init_params(
                    jax.random.PRNGKey(config.seed + 1), config.vision
                )
            vcfg = config.vision
            self._encode_image_fn = jax.jit(
                lambda p, img: vis.encode(p, vcfg, img)
            )
            self.encoder_cache = EncoderCacheManager()
        self._mm_zero: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        # the (1, 1) dummy pair of an engine without vision, made once
        self._mm_none: Optional[Tuple[jax.Array, jax.Array]] = None
        # multi-LoRA adapter tables (static shapes; see lora/adapters.py)
        self.lora = None
        if config.lora_max_adapters > 0:
            from ..lora import LoraAdapterTable

            with self.mesh:
                self.lora = LoraAdapterTable(
                    self.mcfg, config.lora_max_adapters, config.lora_rank,
                    config.lora_targets, dtype=self.mcfg.dtype,
                )
        # disaggregation: KV transfer in/out (engine/transfer.py)
        self.transfer_address: Optional[str] = None
        self._transfer_server = None
        self._transfer_client = None
        # per-chunk commit broadcast for streamed transfer (created with the
        # transfer server; _commit_prefilled_blocks fires it so streaming
        # fetches wake as each prefill chunk's blocks become addressable)
        self.kv_commits = None
        self._probe_load_fn = None  # EPLB load probe, jitted on first use
        self._build_programs()
        if config.ready_single_step:
            self._ready_single_step()

    def _ready_single_step(self) -> None:
        """``TpuEngineConfig.ready_single_step``: ``decode`` over NO row
        (every row inactive: scratch writes, no state, no token), twice: on
        the arrays as they were placed, and on the first call's results,
        which is the variant every later call is (a program whose donated
        arguments came from ``device_put`` is compiled again when they come
        from another program). Leaves the counters as it found them."""
        kept = self._moe_last, list(self._state_counts)
        for _ in range(2):
            self._run_decode([None] * self.cfg.max_batch_size)
        self._moe_last, self._state_counts = kept

    # ------------------------------------------------------ kv transfer wiring
    def _check_transfer_plane(self) -> None:
        """Asked where either end of the plane is wired, not at construction:
        an engine that never transfers is refused nothing."""
        registry.check_supported(self.mcfg, transfer=True)

    async def serve_transfer(self, host: str = "127.0.0.1") -> str:
        """Start the kv_fetch endpoint (prefill side of disaggregation)."""
        if self.cfg.pp > 1:
            # transfer gathers iterate per-layer cache lists; pp stacks them
            raise ValueError("pp serving does not cover KV transfer yet")
        self._check_transfer_plane()
        from ..runtime.request_plane.tcp import TcpRequestServer
        from .transfer import KvCommitSignal, KvTransferServer

        if self.kv_commits is None:
            self.kv_commits = KvCommitSignal()
        srv = KvTransferServer(self, host=host)
        self._kv_transfer_srv = srv
        self._transfer_server = TcpRequestServer(srv.handle, host=host)
        self.transfer_address = await self._transfer_server.start()
        # co-resident clients (same-slice xPyD) find us here and move pages
        # device->device instead of over the wire (transfer.IciKvMover)
        from .transfer import LOCAL_SERVERS

        LOCAL_SERVERS[self.transfer_address] = srv
        return self.transfer_address

    def _get_transfer_client(self):
        if self._transfer_client is None:
            from .transfer import KvTransferClient

            self._check_transfer_plane()
            self._transfer_client = KvTransferClient(self)
        return self._transfer_client

    @property
    def kv_bytes_per_block(self) -> int:
        """Wire/storage bytes of one KV block (the transfer-cost signal
        register_llm advertises for transfer-aware disagg routing)."""
        from ..kvbm.layout import kv_bytes_per_token

        # the layout counts num_layers pages a block; a block holds a page
        # a SLOT (registry.page_slots: fewer than the layers where only some
        # keep pages, more where a (pass, layer) keeps its own)
        held = registry.page_slots(self.mcfg) / self.mcfg.num_layers
        return int(
            kv_bytes_per_token(self.mcfg, self.cfg.block_size, self.cfg.kv_dtype)
            * self.cfg.block_size * held
        )

    def _evacuation_plan(self, st) -> Optional[Dict[str, Any]]:
        """The evacuation reference an error-finish frame carries
        (docs/operations.md §13): the retry's router prices destinations by
        the cost of pulling this worker's sealed KV, and the replacement
        worker replays the plan as its ``kv_transfer`` fetch instead of
        recomputing the prefix. Tier streaming (``tier: True``) serves from
        the host tier, which survives engine-loop death and drain. None
        when the request has nothing fetchable (no transfer server, opted
        out of caching, or no full block computed yet)."""
        if self.transfer_address is None or getattr(st, "no_cache", False):
            return None
        seq = getattr(st, "seq", None)
        if seq is None:
            return None
        try:
            hashes = [int(h) for h in seq.sequence_hashes()]
        except Exception:
            return None
        n_tokens = len(st.req.token_ids) + int(st.produced)
        blocks = min(len(hashes), n_tokens // self.cfg.block_size)
        if blocks <= 0:
            return None
        return {
            "address": self.transfer_address,
            "hashes": hashes[:blocks],
            "num_tokens": blocks * self.cfg.block_size,
            "tier": True,
            "bytes_per_block": int(self.kv_bytes_per_block),
        }

    # ------------------------------------------------------------------ setup
    def _param_shardings(self, params, mcfg):
        """NamedSharding per leaf of a params pytree (arrays or shapes)."""
        specs = registry.param_specs(mcfg)

        def tree(d, table):
            return {
                name: NamedSharding(
                    self.mesh, table.get(name, specs["default"])
                )
                for name in d if name != "layers"
            }

        out = tree(params, specs["top"])
        out["layers"] = [tree(lp, specs["layer"]) for lp in params["layers"]]
        return out

    def _init_params_sharded(self, seed: int, mcfg) -> llama.Params:
        """Random weights born sharded, for meshes of more than one device:
        the init runs as ONE program whose out_shardings are the parameter
        shardings, so every device draws only its own shard — no device
        ever holds the whole model (or its float32 draws), which for a model
        that only fits sharded is the difference between starting and dying
        on chip 0. The draws are the eager init's (threefry is
        partitionable); a value can differ from it in its last bit where XLA
        folds the scale into the cast. Costs one compile per engine, which
        is why a one-device mesh keeps the eager init."""
        init = partial(registry.init_params, cfg=mcfg)
        key = jax.random.PRNGKey(seed)
        shardings = self._param_shardings(jax.eval_shape(init, key), mcfg)
        return jax.jit(init, out_shardings=shardings)(key)

    def _shard_params(self, params: llama.Params, mcfg=None) -> llama.Params:
        specs = registry.param_specs(mcfg if mcfg is not None else self.mcfg)
        mh = self._mh is not None

        def put(x, spec):
            if mh:
                # route through host: every process uploads its own shards of
                # the (identical) host weights; a committed device array from
                # random-init/warm-load is process-local and cannot be put to
                # a mesh that spans processes
                x = np.asarray(x)
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        out: llama.Params = {"layers": []}
        for name, w in params.items():
            if name == "layers":
                continue
            out[name] = put(w, specs["top"].get(name, specs["default"]))
        for lp in params["layers"]:
            slp = {
                name: put(w, specs["layer"].get(name, specs["default"]))
                for name, w in lp.items()
            }
            out["layers"].append(slp)
        return out

    def _init_caches(
        self, mcfg=None, quantized: Optional[bool] = None
    ) -> Tuple[List[jax.Array], List[jax.Array]]:
        mcfg = mcfg if mcfg is not None else self.mcfg
        if quantized is None:
            quantized = self.kv_quantized
        pages = self.cfg.num_blocks
        if self._ring is not None and mcfg is self.mcfg:
            # the summary blocks, whole pages each, above the ring's pages
            pages += (
                self.summary_allocator.num_blocks * self._ring.pages_per_block
            )
        if registry.pooled_keys(mcfg) is not None:
            # one pooled key a block id, a row each of the K pool's pages
            # above the requests' (ops/attention.py, InfLlmQuery)
            pages += att.infllm_pool_pages(pages, self.cfg.block_size)
        # a slot a (pass, layer): a layer's arrays hold a pool a pass, one
        # behind another, and a block id names its page in each
        pages *= registry.page_passes(mcfg)
        shape = (
            pages,
            self.cfg.block_size,
            mcfg.num_kv_heads,
            mcfg.head_dim,
        )
        tp_n = meshlib.tp_size(self.mesh)
        sharding = NamedSharding(
            self.mesh, registry.kv_cache_spec(mcfg, tp_n)
        )
        # one pair of arrays a layer that KEEPS pages (registry.page_layers:
        # every layer, but for a family whose layers are of different kinds),
        # whatever the slots of pages each holds (registry.page_slots)
        n_paged = len(registry.page_layers(mcfg))
        # pages by layer kind: a windowed group's layers have its pool's pages
        # (int8 and a draft's shadow cache are refused more than one group)
        pool = {
            l: g.allocator.num_blocks
            for g in (self._win_groups if mcfg is self.mcfg else ())
            for l in g.layers
        }
        sizes = [pool.get(l, pages) for l in registry.page_layers(mcfg)]
        # a token's shape in each layer's two arrays: ``shape``'s for every
        # family, but for one that shapes a page group's arrays by its layers
        # (registry.page_shapes: a latent of another width a layer kind, and
        # of the second array the one tile a step reads)
        tokens = registry.page_shapes(mcfg)
        # host-side zeros: device_put shards them per-process (jnp.zeros would
        # commit to the local default device — invalid for a multi-host mesh)
        if quantized:
            from ..ops.quant import SCALE_DTYPE, QuantizedKV

            s_sharding = NamedSharding(
                self.mesh, registry.kv_scale_spec(mcfg, tp_n)
            )
            s_shape = (self.cfg.num_blocks, mcfg.num_kv_heads)

            def qzeros():
                return QuantizedKV(
                    jax.device_put(np.zeros(shape, np.int8), sharding),
                    jax.device_put(np.zeros(s_shape, SCALE_DTYPE), s_sharding),
                )

            k = [qzeros() for _ in range(n_paged)]
            v = [qzeros() for _ in range(n_paged)]
            return k, v
        zeros = lambda n, token: np.zeros(  # noqa: E731
            (n, shape[1], *token), mcfg.dtype
        )
        k = [jax.device_put(zeros(n, t[0]), sharding)
             for n, t in zip(sizes, tokens)]
        v = [jax.device_put(zeros(n, t[1]), sharding)
             for n, t in zip(sizes, tokens)]
        return k, v

    def _resolve_use_pallas(self) -> bool:
        """cfg.use_pallas, with None resolved to the auto rule: Mosaic DMA
        slices need the minor dim 128-aligned (head_dim is the page's minor
        dim, so odd head sizes fall back to pure JAX); the shard_map'd
        kernel shards the cache on kv_heads, so fewer kv heads than TP
        shards (MQA / MLA latent) falls back to the GSPMD pure-JAX path.
        Per-row WINDOWS are confirmed on the chip (PR 26, TPU v5e: the
        unified kernel's windowed launch, chunk-start page skip included,
        compiled by Mosaic and held to the float32 reference at 4 kv heads,
        16-token pages, window 1 024, contexts to 7 168, in prefill chunks,
        mixed steps and q_len=1 decode rows), so a windowed MoeConfig rides
        the auto rule. Per-head SINKS and the logit SOFTCAP have only run
        in the interpreter: gpt-oss and gemma stay off the auto rule until
        a chip run holds them too (their modules' ``PALLAS_AUTO``) — an
        explicit use_pallas=True routes their layers through the unified
        launch. pp serving never uses
        Pallas (construction rejects the combination)."""
        if self.cfg.pp > 1:
            return False
        if self.cfg.use_pallas is not None:
            return bool(self.cfg.use_pallas)
        return on_tpu() and self._pallas_auto_ok(self.mcfg)

    def _pallas_auto_ok(self, mcfg) -> bool:
        """The model-side half of the auto rule, shared by the main model
        and a speculative draft (each judged on its own config). What it
        asks of ``head_dim`` and ``num_kv_heads`` is asked of the PAGES the
        attention kernels copy, whatever a family keeps in them: a latent
        held as one head of rank + rope lanes is refused (Mosaic slices HBM
        by whole tiles), one held as rows of 128 lanes (an MlaConfig with an
        indexer, or without one at widths that allow it,
        ``MlaConfig.latent_rows``: ops/attention.py has the layout) is
        admitted, and with it the fused mixed step and the Pallas expert
        multiplication, which ask nothing of the attention layout but ride
        the same switch."""
        return (
            mcfg.head_dim % 128 == 0
            and mcfg.num_kv_heads % meshlib.tp_size(self.mesh) == 0
            and registry.pallas_auto(mcfg)
        )

    def _pp_bodies(self):
        """What differs under pp > 1: the BODIES of the step programs. Thin
        adapters over the shard_map wavefronts of parallel/pp_serving.py, the
        stacked caches the one element of ``k_caches`` / ``v_caches``. What pp
        does not serve (LoRA, vision, slot state, routing counters) is
        refused at construction, and those arguments are ignored here."""
        from ..parallel import pp_serving

        ways = (self.mesh, self.mcfg, self.cfg.pp, self.cfg.tp)

        def body(forward):
            # the bodies' own order of arguments is the forwards'
            def run(params, k_caches, v_caches, *inputs, **_):
                hidden, k_caches[0], v_caches[0] = forward(
                    params, k_caches[0], v_caches[0], *inputs
                )
                return hidden
            return run

        chunk_body = body(pp_serving.make_pp_prefill_forward(*ways))
        rows_body = body(pp_serving.make_pp_decode_forward(*ways))
        embed_body = pp_serving.make_pp_embed_forward(*ways)
        # a chunk of a long embedding input runs as a chunk of a prompt does
        return chunk_body, rows_body, embed_body, chunk_body

    def _build_programs(self) -> None:
        """Every step program is a BODY, which runs the layers and writes
        the cache (``chunk_body``, ``rows_body``, a mixed step's own; under
        pp ``_pp_bodies``), followed by an EPILOGUE that turns ``hidden``
        into tokens (engine/sampling.py ``rows_epilogue``,
        ``first_token_epilogue``). What a program appends to its readback
        (the routing counters, ``pack_step``'s columns, ``_fetchable``) is
        the step's and stays here."""
        cfg, mcfg = self.cfg, self.mcfg
        fwd, logits_fn = self._forward, self._lm_logits
        lora_enabled = self.lora is not None
        quantized = self.kv_quantized
        vision_enabled = cfg.vision is not None
        moe_counted = self._moe_counted
        # model layer -> its place among the layers that keep pages / slot
        # state (None: every layer does, the model's index is the place)
        page_of = registry.layer_index(
            registry.page_layers(mcfg), mcfg.num_layers
        )
        state_of = registry.layer_index(
            registry.state_layers(mcfg), mcfg.num_layers
        ) if self.state is not None else None
        # a slot a (pass, layer): the family's forward runs its stack
        # ``passes`` times and hands ``attend`` the pass (``page_pass``); 1,
        # and no ``page_pass``, for every other family
        passes = registry.page_passes(mcfg)

        def at_pass(page_pass, *ids):
            """Block ids as pass ``page_pass`` holds them: ``page_pass x
            num_blocks`` further on in the layer's arrays (the scratch page
            0 too: every pass has its own), a run of pages still a run; as
            they came where there is no pass (every other family)."""
            if page_pass is None:
                return ids
            return tuple(i + page_pass * cfg.num_blocks for i in ids)

        def pass_loop(k_caches, v_caches):
            """``loop`` of a forward that runs its stack several times a
            token: the passes as ONE traced loop with the page arrays on its
            carry (``attend`` reads and writes the lists in place, as in a
            stack run once), so a program holds the layers' bodies once."""
            def loop(one_pass, x, n):
                def step(t, carry):
                    x, k_caches[:], v_caches[:] = carry
                    x = one_pass(x, t)
                    return x, list(k_caches), list(v_caches)

                x, k_caches[:], v_caches[:] = jax.lax.fori_loop(
                    0, n, step, (x, list(k_caches), list(v_caches))
                )
                return x
            return loop

        # page slot -> pages a chunk, for the layers whose decode rows the
        # decode-only kernel serves: filled as ``rows_attend`` is traced,
        # read by ``_count_paged``
        paged_layers = self._paged_layers = {}
        self._paged_counts = [0, 0]

        def call_fwd(params, tokens, positions, attend, lora_tables, lora_ids,
                     mm_embeds=None, mm_mask=None, moe_stats=None, mix=None,
                     caches=None):
            kw = {}
            if passes > 1 and caches is not None:
                kw["loop"] = pass_loop(*caches)
            if page_of is not None:
                # a family whose layers are of different kinds: a layer's
                # pages by its place among page_layers
                paged = attend

                def attend(q, k_new, v_new, layer_idx, **extra):
                    return paged(q, k_new, v_new, page_of[layer_idx], **extra)
            if mix is not None:
                kw["mix"] = mix
            if moe_stats is not None:
                kw["stats"] = moe_stats
            if lora_enabled:
                from ..lora import make_lora_fn

                kw["lora"] = make_lora_fn(lora_tables, lora_ids)
            if mm_embeds is not None and vision_enabled:
                # splice vision soft tokens over placeholder positions; the
                # gather uses clipped ids (placeholders sit above the vocab)
                safe = jnp.clip(tokens, 0, mcfg.vocab_size - 1)
                base = params["embed"][safe]
                kw["inputs_embeds"] = jnp.where(
                    mm_mask[..., None], mm_embeds.astype(base.dtype), base
                )
                return fwd(params, mcfg, safe, positions, attend, **kw)
            return fwd(params, mcfg, tokens, positions, attend, **kw)

        # the one attention seam (ops/paged_attention.py): the programs below
        # state what rows they have, the seam picks the kernel or the twin
        attn = PagedAttention(
            self.mesh, self.use_pallas, self.kernels_interpreted,
            summary_base=cfg.num_blocks,
        )
        ring = self._ring
        # pages by layer kind: a page layer's place -> its group's view of a
        # row's table (ops/paged_attention.GroupView); {} for a family of
        # one group, whose programs take the tables as they always did
        views = {}
        if self._win_groups:
            place = page_of if page_of is not None else {
                l: l for l in range(mcfg.num_layers)
            }
            main = GroupView(
                0, cfg.max_blocks_per_seq, cfg.block_size, shifted=False
            )
            views = {i: main for i in place.values()}
            for g in self._win_groups:
                views.update({
                    place[l]: GroupView(g.col, g.pages, cfg.block_size)
                    for l in g.layers
                })

        # the second seam: ``mix`` owns a family's slot state
        # (engine/state_cache.py) as ``attend`` owns the pages. The family's
        # two mixing functions come from the registry (``mixers``) and the
        # state's names from ``state_spec``; nothing here names a family.
        # The programs below build a ``mix`` only where they were handed
        # ``state``: a TRACE-time branch, every other family's programs are
        # unchanged. ``state``: name -> one array a state layer, written
        # through in place as the cache lists are
        if self.state is not None:
            from .state_cache import fresh

            mix_chunk, mix_rows = registry.mixers(
                mcfg, self.use_pallas, self.kernels_interpreted
            )
            names = [name for name, _, _ in self.state.spec]

        def chunk_mix(params, state, slot, chunk_start, n_real):
            """One request's chunk: scan from its slot's state (zeros for a
            prompt's first chunk), the identity past ``n_real``."""
            def mix(*xs):
                *xs, l = xs
                i = l if state_of is None else state_of[l]
                held = [state[name][i] for name in names]
                y, *new = mix_chunk(
                    params["layers"][l], mcfg, *xs,
                    *(fresh(a[slot], chunk_start) for a in held), n_real,
                )
                for name, a, n in zip(names, held, new):
                    state[name][i] = a.at[slot].set(n)
                return y
            return mix

        def rows_mix(params, state, live):
            """One token a slot ([B, 1, ...] in and out): rows that are not
            ``live`` leave their slot alone."""
            def mix(*xs):
                *xs, l = xs
                i = l if state_of is None else state_of[l]
                y, *new = mix_rows(
                    params["layers"][l], mcfg, *(x[:, 0] for x in xs),
                    *(state[name][i] for name in names), live,
                )
                for name, n in zip(names, new):
                    state[name][i] = n
                return y[:, None]
            return mix

        procs = cfg.logits_processors

        # host-fetched outputs are pinned fully-replicated: on a single
        # process any addressable layout can be np.asarray'd, but the leader
        # of a multi-process mesh can only fetch data whose every shard is
        # addressable locally. A no-op on one device; an all-gather of a few
        # hundred bytes otherwise.
        repl = NamedSharding(self.mesh, P())

        def _fetchable(x):
            return jax.lax.with_sharding_constraint(x, repl)

        def pack_step(toks, lps, tlp_vals, tlp_ids, moe=None):
            """[B] toks/lps + [B,K] top-logprob rows -> one [B, 2+2K] f32 row
            (token ids are exact in f32 below 2^24) so the host pays a single
            device->host fetch per horizon. ``moe`` ([3], the step's routing
            counters; [6] with an indexer's) rides as that many more columns,
            the same in every row."""
            cols = [
                toks.astype(jnp.float32)[:, None],
                lps[:, None],
                tlp_ids.astype(jnp.float32),
                tlp_vals,
            ]
            if moe is not None:
                cols.append(
                    jnp.broadcast_to(moe[None], (toks.shape[0], moe.shape[0]))
                )
            return jnp.concatenate(cols, axis=-1)

        def routing_stats(valid, decode_rows=None):
            """A collector for this forward's routing counts (one-chip MoE),
            or None: a TRACE-time branch. ``decode_rows``: which of the rows
            are decode rows, where not all are (a mixed step)."""
            if not moe_counted:
                return None
            return moe_lib.RoutingStats(valid, decode_rows)

        if cfg.sp > 1:
            from ..parallel import ring as ringlib

        def real_rows(k_new, v_new, positions, total_len):
            """A chunk's K/V as the pool takes them. Under an 8-bit pool the
            bucket's PADDING rows are zeroed before quantize-on-write: they
            share the last real block (token 0 at position max_context-1)
            and would enter its amax and coarsen the real tokens. Never
            attended (every mask keys off total_len), so zeros are safe."""
            if not quantized:
                return k_new, v_new
            valid = (positions < total_len)[:, None, None]
            return jnp.where(valid, k_new, 0.0), jnp.where(valid, v_new, 0.0)

        def write_slots(block_tables, positions, active):
            """(block, offset) where each live row's token at ``positions``
            is written; scratch block 0 for the others."""
            bs = cfg.block_size
            # under a ring a position's page is its window's: the entry wraps
            inside = positions if ring is None else positions % ring.positions
            blocks = jnp.where(
                active,
                jnp.take_along_axis(
                    block_tables, (inside // bs)[:, None], axis=1
                )[:, 0],
                0,
            )
            return blocks, jnp.where(active, positions % bs, 0)

        def rows_attend(attn, k_caches, v_caches, block_tables, seq_lens,
                        write_blocks, write_offsets):
            """``attend`` of decode rows ([B, 1, ...] a layer): the fed
            token's KV written, then attended at the end of its context."""
            def attend(q, k_new, v_new, layer_idx, page_pass=None, **extra):
                # the rows as the layer's own page group holds them; a
                # family of one group: as they came
                tables, lens, pages = (
                    views[layer_idx].rows(block_tables, seq_lens, write_blocks > 0)
                    if views else (block_tables, seq_lens, write_blocks)
                )
                tables, pages = at_pass(page_pass, tables, pages)
                kc, vc = att.write_decode_kv(
                    k_caches[layer_idx], v_caches[layer_idx],
                    k_new[:, 0], v_new[:, 0], pages, write_offsets,
                )
                if "eva" in extra:
                    # a row whose token filled its page: the page's summary
                    kc, vc = attn.summarise_rows(
                        kc, vc, tables, lens, pages, write_offsets,
                        extra["eva"],
                    )
                if "infllm" in extra:
                    # ... or the pooled key of the page before it
                    kc = attn.pool_rows(
                        kc, tables, lens, pages, write_offsets,
                        extra["infllm"],
                    )
                k_caches[layer_idx], v_caches[layer_idx] = kc, vc
                cp = attn.decode_chunk_pages(kc, tables, extra)
                if cp is not None:
                    # known once traced: this layer's decode rows are the
                    # decode-only kernel's, which walks chunks of ``cp`` pages
                    # (a launch a SLOT where a layer keeps one a pass)
                    paged_layers.update(
                        {layer_idx: cp} if page_pass is None
                        else {(layer_idx, t): cp for t in range(passes)}
                    )
                out = attn.decode(q[:, 0], kc, vc, tables, lens, **extra)
                return out[:, None]
            return attend

        def unit(h):
            h = h.astype(jnp.float32)
            return h / jnp.maximum(jnp.linalg.norm(h), 1e-9)

        # ---- the bodies: (params, caches, inputs) -> hidden, the cache
        # lists written through in place
        def chunk_body(params, k_caches, v_caches, tokens, positions,
                       block_table, new_block_ids, total_len, *, chunk_start,
                       lora_tables, lora_id, mm_embeds, mm_mask, mix):
            """ONE chunk of one prompt ([S_pad] tokens, the whole prompt when
            it fits a bucket): its pages written, its rows attended over the
            prefix and the chunk."""

            def attend(q, k_new, v_new, layer_idx, page_pass=None, **extra):
                # extra: per-layer attention variants the model opts into
                # (sliding ``window``, per-head ``sinks`` — models/gptoss.py);
                # plain families pass nothing and nothing changes
                # the chunk as the layer's own page group holds it; a family
                # of one group: as it came
                table, start, end, pos, ids = (
                    views[layer_idx].chunk(
                        block_table, chunk_start, total_len, positions,
                        new_block_ids,
                    ) if views else (
                        block_table, chunk_start, total_len, positions,
                        new_block_ids,
                    )
                )
                table, ids = at_pass(page_pass, table, ids)
                kc, vc = attn.write_chunk(
                    k_caches[layer_idx], v_caches[layer_idx],
                    *real_rows(k_new, v_new, positions, total_len), ids,
                )
                if "eva" in extra:
                    # the summaries of the chunk's whole pages
                    kc, vc = attn.summarise_chunk(
                        kc, vc, k_new, v_new, table, start, end,
                        extra["eva"],
                    )
                if "infllm" in extra:
                    # the pooled keys the chunk's whole pages make final
                    kc = attn.pool_chunk(
                        kc, k_new, table, start, end, extra["infllm"]
                    )
                k_caches[layer_idx], v_caches[layer_idx] = kc, vc
                if cfg.sp > 1:
                    # context-parallel chunk attention: queries + chunk KV
                    # shard over the sp axis and rotate around the ring; the
                    # cached prefix is attended locally (parallel/ring.py).
                    # gather_kv dequantizes int8 caches, so the ring path
                    # rides quantization transparently.
                    k_ctx, v_ctx = att.gather_kv(kc, vc, block_table)
                    return ringlib.ring_extend_attention(
                        self.mesh, q, k_new, v_new, k_ctx, v_ctx,
                        positions, chunk_start, chunk_start,
                    )
                return attn.chunk(q, kc, vc, table, start, end, pos, **extra)

            return call_fwd(
                params, tokens, positions, attend, lora_tables, lora_id,
                mm_embeds=mm_embeds, mm_mask=mm_mask, mix=mix,
                caches=(k_caches, v_caches),
            )

        def rows_body(params, k_caches, v_caches, tokens, positions,
                      block_tables, seq_lens, write_blocks, write_offsets, *,
                      lora_tables, lora_ids, moe_stats, state, live=None):
            """The decode rows: one fed token a slot ([B] each), written at
            ``write_blocks`` / ``write_offsets`` (scratch block 0 for an empty
            row) and attended at the end of its context. ``live``: the rows
            whose slot ``state`` advances (None: every row with a context)."""
            return call_fwd(
                params, tokens[:, None], positions[:, None],
                rows_attend(attn, k_caches, v_caches, block_tables, seq_lens,
                            write_blocks, write_offsets),
                lora_tables, lora_ids, moe_stats=moe_stats,
                mix=rows_mix(
                    params, state, seq_lens > 0 if live is None else live
                ) if state else None,
                caches=(k_caches, v_caches),
            )[:, 0]  # [B, H]

        def embed_body(params, tokens, positions):
            """Dense causal forward, no KV pages touched; padded tail
            positions can't affect earlier queries (causal)."""

            def attend(q, k_new, v_new, layer_idx, eva=None, infllm=None,
                       page_pass=None, **extra):
                # ``page_pass``: no pages here, every pass attends its own
                if eva is not None:  # a whole sequence from nothing
                    return att.eva_attention(q, k_new, v_new, eva)
                if infllm is not None:
                    return att.infllm_attention(q, k_new, v_new, infllm)
                return att.causal_attention(q, k_new, v_new, **extra)

            return fwd(params, mcfg, tokens, positions, attend)  # [S, H]

        def plain_chunk(forward, model, seam, params, k_caches, v_caches,
                        tokens, positions, block_table, new_block_ids,
                        total_len):
            """A chunk through a model's bare forward (an embedding input
            past the largest bucket, into TEMPORARY pages; the draft's share
            of a prompt): its KV written, attended over the gathered prefix."""

            def attend(q, k_new, v_new, layer_idx, page_pass=None, **extra):
                table, ids = at_pass(page_pass, block_table, new_block_ids)
                kc, vc = att.write_prefill_kv(
                    k_caches[layer_idx], v_caches[layer_idx],
                    *real_rows(k_new, v_new, positions, total_len), ids,
                )
                if "infllm" in extra:
                    kc = seam.pool_chunk(
                        kc, k_new, table, positions[0], total_len,
                        extra["infllm"],
                    )
                k_caches[layer_idx], v_caches[layer_idx] = kc, vc
                # a chunk's first token is real: its position is the start
                return seam.chunk(
                    q, kc, vc, table, positions[0], total_len,
                    positions, **extra
                )

            return forward(params, model, tokens, positions, attend)

        embed_chunk_body = partial(plain_chunk, fwd, mcfg, attn)

        if cfg.pp > 1:
            # only the bodies differ: the programs below are pp's too
            chunk_body, rows_body, embed_body, embed_chunk_body = (
                self._pp_bodies()
            )

        # ---- the programs. _wire_multihost and tests/test_step_upload.py
        # know their arguments and results by POSITION
        def prefill(params, k_caches, v_caches, counts, tokens, positions,
                    new_block_ids, step, seeds, temps, top_ks, top_ps, min_ps,
                    pres, freqs, reps, prompt_masks, lora_tables, lora_ids,
                    proc_masks, mm_embeds, mm_mask,
                    g_active=None, g_class=None, g_trans=None, state=None):
            # step: the chunk's per-step values (step_args.py: its block-table
            # row, span, slot and switches); the sampling arrays are the
            # per-slot [B] ones every program takes, read at ``slot``
            a = step_args.unpack(step, cfg.max_batch_size)
            smp = SlotSampling(
                seeds, temps, top_ks, top_ps, min_ps, pres, freqs, reps,
                prompt_masks, proc_masks, g_active, g_class, g_trans,
            )
            hidden = chunk_body(
                params, k_caches, v_caches, tokens, positions, a.table_row,
                new_block_ids, a.total_len, chunk_start=a.chunk_start,
                lora_tables=lora_tables, lora_id=lora_ids[a.slot],
                mm_embeds=mm_embeds, mm_mask=mm_mask,
                mix=None if state is None else chunk_mix(
                    params, state, a.slot, a.chunk_start,
                    a.total_len - a.chunk_start,
                ),
            )
            counts, *first = first_token_epilogue(
                partial(logits_fn, params, mcfg), hidden, positions,
                a.total_len, a.slot, a.is_final, a.c_lp_need, smp, counts,
                procs=procs, g_state=a.c_g_state,
            )
            return (k_caches, v_caches, counts, *map(_fetchable, first))

        def decode(params, k_caches, v_caches, counts, step, block_tables,
                   seeds, temps, top_ks, top_ps, min_ps, pres, freqs, reps,
                   prompt_masks, lora_tables, lora_ids, proc_masks,
                   g_active=None, g_class=None, g_trans=None, state=None):
            # step: the [B] per-step rows (step_args.py)
            a = step_args.unpack(step, cfg.max_batch_size)
            smp = SlotSampling(
                seeds, temps, top_ks, top_ps, min_ps, pres, freqs, reps,
                prompt_masks, proc_masks, g_active, g_class, g_trans,
            )
            moe_stats = routing_stats(a.seq_lens > 0)
            hidden = rows_body(
                params, k_caches, v_caches, a.tokens, a.positions,
                block_tables, a.seq_lens, a.write_blocks, a.write_offsets,
                lora_tables=lora_tables, lora_ids=lora_ids,
                moe_stats=moe_stats, state=state,
            )
            toks, lps, tlp_vals, tlp_ids, counts, _ = rows_epilogue(
                logits_fn(params, mcfg, hidden), smp, counts, a.steps,
                a.seq_lens, a.lp_need, procs=procs, g_state=a.g_state,
            )
            if moe_stats is not None:
                # the counters ride the logprob readback: lps is [B + 3]
                lps = jnp.concatenate([lps, moe_stats.reduce()])
            return (k_caches, v_caches, counts,
                    *map(_fetchable, (toks, lps, tlp_vals, tlp_ids)))

        def decode_multi(params, k_caches, v_caches, counts, tokens, seq_lens,
                         block_tables, active, seeds, steps0, temps, top_ks,
                         top_ps, min_ps, pres, freqs, reps, prompt_masks,
                         lp_need, lora_tables, lora_ids, proc_masks,
                         g_active=None, g_state=None, g_class=None,
                         g_trans=None, state=None, max_new=None):
            """cfg.decode_steps decode iterations in one program: each step
            writes the fed token's KV, attends, samples, and feeds the sample
            back — tokens only reach the host once per horizon. seq_lens==0
            slots (inactive) write to scratch block 0 and are discarded.

            Returns the per-step results packed into ONE f32 array
            [N, B, 2+2K] (sampled token, its logprob, top-K logprob rows),
            plus the device-resident carry (tokens/seq_lens/steps) that lets
            the loop dispatch the next horizon without any host round-trip."""
            smp = SlotSampling(
                seeds, temps, top_ks, top_ps, min_ps, pres, freqs, reps,
                prompt_masks, proc_masks, g_active, g_class, g_trans,
            )
            need_pen = counts_need(procs, pres, freqs, reps, proc_masks)

            def one_step(carry, s):
                # slot state, where the family has it, rides the carry
                # behind the pages: ``st`` is {} for every other family
                k_caches, v_caches, counts, tokens, seq_lens, g_st, st = carry
                positions = jnp.maximum(seq_lens - 1, 0)
                write_blocks, write_offsets = write_slots(
                    block_tables, positions, active
                )
                moe_stats = routing_stats(active)
                hidden = rows_body(
                    params, k_caches, v_caches, tokens, positions,
                    block_tables, seq_lens, write_blocks, write_offsets,
                    lora_tables=lora_tables, lora_ids=lora_ids,
                    moe_stats=moe_stats, state=st,
                    # a row that has sampled what its request asked for (the
                    # host learns it a horizon late) leaves its slot alone:
                    # a finished slot holds the state after its last fed token
                    live=active & (steps0 + s < max_new) if st else None,
                )
                toks, lps, tlp_vals, tlp_ids, counts, g_st = rows_epilogue(
                    logits_fn(params, mcfg, hidden), smp, counts, steps0 + s,
                    seq_lens, lp_need, active=active, procs=procs,
                    g_state=g_st, advance_guided=True, need=need_pen,
                )
                seq_lens = seq_lens + active.astype(jnp.int32)
                return (
                    (k_caches, v_caches, counts, toks, seq_lens, g_st, st),
                    pack_step(
                        toks, lps, tlp_vals, tlp_ids,
                        moe=None if moe_stats is None else moe_stats.reduce(),
                    ),
                )

            g0 = g_state if g_state is not None else jnp.zeros_like(tokens)
            (k_caches, v_caches, counts, tokens, seq_lens, g_out, st), packed = (
                jax.lax.scan(
                    one_step,
                    (k_caches, v_caches, counts, tokens, seq_lens, g0,
                     {} if state is None else state),
                    jnp.arange(cfg.decode_steps),
                )
            )
            if state is not None:
                state.update(st)
            next_steps = steps0 + jnp.where(active, cfg.decode_steps, 0)
            out = (
                k_caches, v_caches, counts, _fetchable(packed),
                tokens, seq_lens, next_steps,
            )
            return out + (g_out,) if g_active is not None else out

        def mixed_step(params, k_caches, v_caches, counts,
                       c_tokens, c_positions, c_new_block_ids, step, carry,
                       block_tables, seeds, temps, top_ks, top_ps, min_ps,
                       pres, freqs, reps, prompt_masks, lora_tables,
                       lora_ids, proc_masks,
                       g_active=None, g_class=None, g_trans=None, state=None):
            """ONE fused continuous-batching step: a prefill chunk of one
            sequence (c_* args — the prefill() conventions) rides along with
            the resident decode batch (d_* args — the decode() conventions)
            through a single forward. The packed token buffer is
            [S_pad + B]: the chunk's bucketed tokens first, then one decode
            token per slot; attention is ONE unified ragged launch where row
            0 is the chunk (query_len = chunk_len) and rows 1..B are the
            decode slots (query_len = 1, or 0 when inactive). Behind the
            forward run the epilogues decode() and prefill() run, so mixed
            steps are token-identical to the split dispatches. Both
            halves' per-step values arrive in ``step`` (step_args.py).

            A mixed step is a link of the decode chain: ``carry`` is the
            sampled tokens of the mixed step launched before it, still on
            the device, and the rows ``step`` marks ``carried`` are fed
            from there (the loop launched this step before it read them);
            every other row's token is the host's. Behind today's results
            it returns its own carry as a horizon's (``toks`` itself,
            ``seq_lens`` and ``steps`` advanced one token a live row)."""
            a = step_args.unpack(step, cfg.max_batch_size)
            d_tokens = jnp.where(a.carried != 0, carry, a.tokens)
            smp = SlotSampling(
                seeds, temps, top_ks, top_ps, min_ps, pres, freqs, reps,
                prompt_masks, proc_masks, g_active, g_class, g_trans,
            )
            S_pad = c_tokens.shape[0]
            B = d_tokens.shape[0]
            chunk_len = a.total_len - a.chunk_start
            tokens = jnp.concatenate([c_tokens, d_tokens])
            positions = jnp.concatenate([c_positions, a.positions])
            active = a.seq_lens > 0

            def attend(q, k_new, v_new, layer_idx, page_pass=None, **extra):
                # extra: per-layer attention variants (sliding window,
                # per-head sinks, softcap — gpt-oss/gemma) thread straight
                # into the unified launch as per-row attributes. The chunk's
                # whole pages go through the seam: on the view the launch
                # below reads, so nothing re-tiles the pool between
                # chunk and rows as the layer's own page group holds them; a
                # family of one group: as they came
                table, c_end, ids, rows, lens, pages = (
                    a.table_row, a.total_len, c_new_block_ids, block_tables,
                    a.seq_lens, a.write_blocks,
                )
                if views:
                    view = views[layer_idx]
                    table, _, c_end, _, ids = view.chunk(
                        table, a.chunk_start, c_end, c_positions, ids
                    )
                    rows, lens, pages = view.rows(rows, lens, pages > 0)
                table, ids, rows, pages = at_pass(
                    page_pass, table, ids, rows, pages
                )
                kc, vc = attn.write_chunk(
                    k_caches[layer_idx], v_caches[layer_idx],
                    *real_rows(
                        k_new[:S_pad], v_new[:S_pad], c_positions, a.total_len
                    ),
                    ids,
                )
                kc, vc = att.write_decode_kv(
                    kc, vc, k_new[S_pad:], v_new[S_pad:], pages,
                    a.write_offsets,
                )
                if "eva" in extra:
                    # the chunk's whole pages, and the rows' filled ones
                    kc, vc = attn.summarise_chunk(
                        kc, vc, k_new[:S_pad], v_new[:S_pad], table,
                        a.chunk_start, c_end, extra["eva"],
                    )
                    kc, vc = attn.summarise_rows(
                        kc, vc, rows, lens, pages, a.write_offsets,
                        extra["eva"],
                    )
                if "infllm" in extra:
                    # the pooled keys the chunk and the rows made final
                    kc = attn.pool_chunk(
                        kc, k_new[:S_pad], table, a.chunk_start, c_end,
                        extra["infllm"],
                    )
                    kc = attn.pool_rows(
                        kc, rows, lens, pages, a.write_offsets,
                        extra["infllm"],
                    )
                k_caches[layer_idx], v_caches[layer_idx] = kc, vc
                tables = jnp.concatenate([table[None], rows], axis=0)
                q_starts = jnp.concatenate([
                    jnp.zeros((1,), jnp.int32),
                    S_pad + jnp.arange(B, dtype=jnp.int32),
                ])
                q_lens = jnp.concatenate([
                    chunk_len[None].astype(jnp.int32),
                    active.astype(jnp.int32),
                ])
                row_lens = jnp.concatenate([
                    c_end[None].astype(jnp.int32),
                    lens.astype(jnp.int32),
                ])
                return attn.ragged(
                    q, kc, vc, tables, q_starts, q_lens, row_lens, **extra
                )

            if lora_enabled:
                # per-row adapter indices threaded through the packed
                # buffer: the chunk's tokens carry its slot's adapter, each
                # decode token its own — batched LoRA rides the same launch
                # (lora/adapters.make_lora_fn per-token branch)
                packed_lora_ids = jnp.concatenate([
                    jnp.full((S_pad,), lora_ids[a.slot], jnp.int32),
                    lora_ids.astype(jnp.int32),
                ])
            else:
                packed_lora_ids = lora_ids
            moe_stats = routing_stats(
                jnp.concatenate([c_positions < a.total_len, active]),
                jnp.concatenate([jnp.zeros((S_pad,), bool), active]),
            )
            mix = None
            if state is not None:
                # the chunk's row scans, the decode rows advance one token:
                # the chunk's slot is prefilling, so never a live decode row
                c_mix = chunk_mix(params, state, a.slot, a.chunk_start, chunk_len)
                d_mix = rows_mix(params, state, active)

                def mix(*xs):
                    *xs, l = xs
                    return jnp.concatenate([
                        c_mix(*(x[:S_pad] for x in xs), l),
                        d_mix(*(x[S_pad:, None] for x in xs), l)[:, 0],
                    ])

            hidden = call_fwd(
                params, tokens, positions, attend, lora_tables,
                packed_lora_ids, moe_stats=moe_stats, mix=mix,
                caches=(k_caches, v_caches),
            )  # [S_pad + B, H]

            # the decode rows' tail, as decode()
            toks, lps, tlp_vals, tlp_ids, counts, _ = rows_epilogue(
                logits_fn(params, mcfg, hidden[S_pad:]), smp, counts, a.steps,
                a.seq_lens, a.lp_need, active=active, procs=procs,
                g_state=a.g_state,
            )
            if moe_stats is not None:
                # as in decode(): lps is [B + 3], chunk rows counted too
                lps = jnp.concatenate([lps, moe_stats.reduce()])
            # the chunk's tail, as prefill()
            counts, *first = first_token_epilogue(
                partial(logits_fn, params, mcfg), hidden, c_positions,
                a.total_len, a.slot, a.is_final, a.c_lp_need, smp, counts,
                procs=procs, g_state=a.c_g_state,
            )
            read = tuple(map(_fetchable, (toks, lps, tlp_vals, tlp_ids, *first)))
            advanced = active.astype(jnp.int32)
            return (k_caches, v_caches, counts, *read,
                    a.seq_lens + advanced, a.steps + advanced)

        def reset_slot(prompt_masks, counts, slot, row):
            return prompt_masks.at[slot].set(row), counts.at[slot].set(0)

        def embed(params, tokens, positions, last_idx):
            """Pooled forward for /v1/embeddings (reference: the Embedding
            model type served by http/service/openai.rs:641): last-token
            hidden state, L2-normalized."""
            hidden = embed_body(params, tokens, positions)
            return _fetchable(unit(hidden[last_idx]))

        def embed_chunk(params, k_caches, v_caches, tokens, positions,
                        block_table, new_block_ids, total_len, last_idx,
                        is_final):
            """Chunked pooled forward for inputs past the largest bucket:
            each chunk's KV into TEMPORARY pages (the caller's, never
            committed); the final chunk returns the pooled vector."""
            hidden = embed_chunk_body(
                params, k_caches, v_caches, tokens, positions, block_table,
                new_block_ids, total_len,
            )
            vec = jax.lax.cond(
                is_final,
                lambda: unit(hidden[last_idx]),
                lambda: jnp.zeros((mcfg.hidden_size,), jnp.float32),
            )
            return k_caches, v_caches, _fetchable(vec)

        # ---- speculative decoding programs (docs/speculative_decoding.md) --
        # Correctness rests on two paged-cache properties: (a) overwrite-is-
        # rollback — rejected candidate positions hold stale KV that is never
        # attended (every mask keys off seq_lens) and is overwritten in place
        # when the sequence reaches that position for real; (b) the bonus
        # token is capped so the advance per round is <= spec_k, which keeps
        # the draft cache's coverage invariant (the draft writes positions
        # start..start+k-1 each round, so the next round's reads never
        # outrun its writes) and keeps a horizon's total advance within
        # _prepare_horizon's decode_steps block booking.
        if self.cfg.spec_draft is not None:
            dcfg = self.cfg.spec_draft
            draft_fwd = self._draft_forward
            draft_logits = self._draft_logits
            sk = self.cfg.spec_k
            R = self._spec_rounds
            B = self.cfg.max_batch_size
            # the draft asks a seam of its own, built from the auto rule on
            # ITS config (head_dim, kv heads vs tp, not a family off the rule:
            # gpt-oss and gemma drafts keep the pure-JAX twins even under a
            # Pallas main engine, forced or not)
            draft_attn = PagedAttention(
                self.mesh, self.use_pallas and self._pallas_auto_ok(dcfg),
                self.kernels_interpreted,
            )

            def draft_prefill_chunk(draft_params, dkc, dvc, tokens, positions,
                                    block_table, new_block_ids, total_len):
                """Write one bucketed chunk of the prompt's DRAFT KV (no
                sampling): same chunk/padding conventions as the main
                prefill so the host reuses _chunk_arrays verbatim."""
                plain_chunk(
                    draft_fwd, dcfg, draft_attn, draft_params, dkc, dvc,
                    tokens, positions, block_table, new_block_ids, total_len,
                )
                return dkc, dvc

            def spec_multi(params, draft_params, k_caches, v_caches, dkc, dvc,
                           tokens, seq_lens, block_tables, active, steps0,
                           lora_tables, lora_ids):
                """R speculative rounds in one program. Each round: sk greedy
                draft steps over the shadow cache, ONE main forward verifying
                the sk+1 candidate positions (query_len=sk+1 rows of the
                unified ragged kernel — the same launch mixed batching
                uses), then vectorized accept — advance n_match+1 capped at
                sk tokens per row. Packed result [R, B, 1+2sk]: advance count, the sk
                verified tokens, their logprobs. Carry (tokens/seq_lens/
                steps) matches decode_multi's, so spec horizons chain with
                normal ones."""

                def one_round(carry, _):
                    k_caches, v_caches, dkc, dvc, tokens, seq_lens = carry

                    def draft_step(dc, j):
                        dkc, dvc, dt = dc
                        pos = jnp.maximum(seq_lens - 1, 0) + j
                        attend = rows_attend(
                            draft_attn, dkc, dvc, block_tables, seq_lens + j,
                            *write_slots(block_tables, pos, active),
                        )
                        hidden = draft_fwd(
                            draft_params, dcfg, dt[:, None], pos[:, None],
                            attend,
                        )
                        logits = draft_logits(draft_params, dcfg, hidden[:, 0])
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        return (dkc, dvc, nxt), nxt

                    (dkc, dvc, _), drafts = jax.lax.scan(
                        draft_step, (dkc, dvc, tokens), jnp.arange(sk)
                    )
                    cand = jnp.concatenate(
                        [tokens[:, None], drafts.T], axis=1
                    )  # [B, sk+1]
                    start = jnp.maximum(seq_lens - 1, 0)
                    pos = start[:, None] + jnp.arange(sk + 1)[None, :]

                    def attend(q, k_new, v_new, layer_idx, **extra):
                        kc2, vc2 = k_caches[layer_idx], v_caches[layer_idx]
                        for s in range(sk + 1):
                            kc2, vc2 = att.write_decode_kv(
                                kc2, vc2, k_new[:, s], v_new[:, s],
                                *write_slots(block_tables, start + s, active),
                            )
                        k_caches[layer_idx], v_caches[layer_idx] = kc2, vc2
                        # verify rows: sk+1 candidate tokens at each
                        # row's context tail (window/sink/softcap included)
                        return attn.verify(
                            q, kc2, vc2, block_tables,
                            jnp.where(active, seq_lens + sk, 0), **extra
                        )

                    hidden = call_fwd(
                        params, cand, pos, attend, lora_tables, lora_ids
                    )  # [B, sk+1, H]
                    logits = logits_fn(
                        params, mcfg, hidden.reshape(B * (sk + 1), -1)
                    ).reshape(B, sk + 1, -1)
                    m = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    lps = jnp.max(
                        jax.nn.log_softmax(
                            logits.astype(jnp.float32), axis=-1
                        ),
                        axis=-1,
                    )  # logprob of the greedy token at each position
                    match = m[:, :sk] == drafts.T
                    n_acc = jnp.sum(
                        jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
                    )
                    adv = jnp.where(active, jnp.minimum(n_acc + 1, sk), 0)
                    carry_tok = jnp.where(
                        active,
                        jnp.take_along_axis(
                            m, jnp.maximum(adv - 1, 0)[:, None], axis=1
                        )[:, 0],
                        tokens,
                    )
                    packed_round = jnp.concatenate(
                        [
                            adv.astype(jnp.float32)[:, None],
                            m[:, :sk].astype(jnp.float32),
                            lps[:, :sk],
                        ],
                        axis=-1,
                    )  # [B, 1+2sk]
                    return (
                        (k_caches, v_caches, dkc, dvc, carry_tok,
                         seq_lens + adv),
                        packed_round,
                    )

                (k_caches, v_caches, dkc, dvc, tokens, seq_lens), packed = (
                    jax.lax.scan(
                        one_round,
                        (k_caches, v_caches, dkc, dvc, tokens, seq_lens),
                        None,
                        length=R,
                    )
                )
                next_steps = steps0 + jnp.sum(
                    packed[..., 0], axis=0
                ).astype(jnp.int32)
                return (
                    k_caches, v_caches, dkc, dvc, _fetchable(packed),
                    tokens, seq_lens, next_steps,
                )

            self._draft_prefill_fn = jax.jit(
                draft_prefill_chunk, donate_argnums=(1, 2)
            )
            self._spec_multi_fn = jax.jit(
                spec_multi, donate_argnums=(2, 3, 4, 5)
            )

        def step_program(fn):
            """A step program jitted with its pages and counts donated. A
            family with slot state gets the same program with the state
            taken fifth, returned fourth and donated too; the call sites
            stay as they are (the state is handed in and taken back here),
            and every other family's jitted program is the one it was."""
            if self.state is None:
                return jax.jit(fn, donate_argnums=(1, 2, 3))

            @wraps(fn)  # the program keeps its name on the trace
            def with_state(params, k_caches, v_caches, counts, state, *rest, **kw):
                out = fn(params, k_caches, v_caches, counts, *rest, state=state, **kw)
                return out[:3] + (state,) + out[3:]

            jitted = jax.jit(with_state, donate_argnums=(1, 2, 3, 4))

            def call(*args, **kw):
                out = jitted(*args[:4], self.state.arrays, *args[4:], **kw)
                self.state.arrays = out[3]
                return out[:3] + out[4:]

            call.jitted = jitted  # the program itself (tests lower it)
            return call

        self._embed_chunk_fn = jax.jit(embed_chunk, donate_argnums=(1, 2))
        if cfg.pp == 1:  # the fused step has a body of its own, and not pp's
            self._mixed_fn = step_program(mixed_step)
        self._prefill_fn = step_program(prefill)
        self._decode_fn = step_program(decode)
        self._decode_multi_fn = step_program(decode_multi)
        self._reset_slot_fn = jax.jit(reset_slot, donate_argnums=(0, 1))
        self._embed_fn = jax.jit(embed)
        if self._mh is not None:
            self._wire_multihost()

    def _wire_multihost(self) -> None:
        """Register every jitted op with the dispatch-replay table.

        ``state_in`` arg positions are the engine-owned globally-sharded
        arrays a follower substitutes with its OWN handles; ``state_out``
        output positions are what both sides store back (the donated caches
        and the device-resident decode carry). Everything else crosses the
        control channel as host numpy — in multi-controller JAX plain numpy
        inputs shard consistently on every process, while a committed
        single-device array cannot feed a mesh-spanning program (which is why
        the leader wrapper also downgrades its own args to numpy).
        """

        # a replayed array's name -> the attribute that holds it
        held = {
            "k": "k_caches", "v": "v_caches",
            "counts": "output_counts", "pmasks": "prompt_masks",
        }
        if self.cfg.spec_draft is not None:
            held.update(dk="draft_k_caches", dv="draft_v_caches")
        if self.guided_enabled:
            held.update(
                g_active_dev="_g_dev_active", g_class_dev="_g_dev_class",
                g_trans_dev="_g_dev_trans",
            )
        state_get = {k: partial(getattr, self, a) for k, a in held.items()}
        state_set = {k: partial(setattr, self, a) for k, a in held.items()}
        state_get.update(params=lambda: self.params, lora=self._lora_tables)
        if self.cfg.spec_draft is not None:
            state_get["draft_params"] = lambda: self.draft_params
        if self._eplb_enabled:
            # EPLB rebalance swaps the whole params pytree (one replayed op)
            state_set["params"] = partial(setattr, self, "params")
        ops = self._mh.router.table(
            ns=self._mh_ns, state_get=state_get, state_set=state_set,
        )
        # guided-arg positions appended to the sampler signatures when the
        # feature is compiled in (engine _build_programs); g_state travels
        # by value (resync) or as the carry sentinel
        g_prefill = (
            # the chunk's resume state travels by value, in the step buffer
            {22: "g_active_dev", 23: "g_class_dev", 24: "g_trans_dev"}
            if self.guided_enabled else {}
        )
        g_decode = (
            {18: "g_active_dev", 19: "g_class_dev", 20: "g_trans_dev"}
            if self.guided_enabled else {}
        )
        g_multi = (
            {22: "g_active_dev", 24: "g_class_dev", 25: "g_trans_dev"}
            if self.guided_enabled else {}
        )
        ops.register(
            "prefill", self._prefill_fn,
            state_in={0: "params", 1: "k", 2: "v", 3: "counts",
                      16: "pmasks", 17: "lora", **g_prefill},
            state_out={0: "k", 1: "v", 2: "counts"},
        )
        ops.register(
            "decode", self._decode_fn,
            state_in={0: "params", 1: "k", 2: "v", 3: "counts",
                      14: "pmasks", 15: "lora", **g_decode},
            state_out={0: "k", 1: "v", 2: "counts"},
        )
        ops.register(
            "decode_multi", self._decode_multi_fn,
            state_in={0: "params", 1: "k", 2: "v", 3: "counts",
                      17: "pmasks", 19: "lora", **g_multi},
            state_out={0: "k", 1: "v", 2: "counts", 4: "carry_tokens",
                       5: "carry_seq_lens", 6: "carry_steps",
                       **({7: "carry_g"} if self.guided_enabled else {})},
            # tokens/seq_lens/steps arrive either as a host resync (numpy →
            # by value) or as the previous horizon's device carry (jax.Array
            # → sentinel; the follower substitutes its stored carry)
            carry_in={4: "carry_tokens", 5: "carry_seq_lens", 9: "carry_steps",
                      **({23: "carry_g"} if self.guided_enabled else {})},
        )
        if self._eplb_enabled:
            # EPLB rebalance as ONE replayed op: every MoE layer's stacked
            # plan (gather sources + routing tables) applies in a single
            # jitted params update, sharding pinned so the expert dim stays
            # on the EP axis on every process
            especs = registry.param_specs(self.mcfg)["layer"]
            esh = {
                k: NamedSharding(self.mesh, especs[k])
                for k in ("w_gate", "w_up", "w_down")
            }

            def eplb_apply_all(params, srcs, slots, nreps):
                # srcs [n_moe, E+R], slots [n_moe, E, R+1], nreps [n_moe, E]
                layers = []
                j = 0
                for lp in params["layers"]:
                    if "eplb_slots" not in lp:
                        layers.append(lp)
                        continue
                    new = dict(lp)
                    for k in ("w_gate", "w_up", "w_down"):
                        new[k] = jax.lax.with_sharding_constraint(
                            lp[k][srcs[j]], esh[k]
                        )
                    new["eplb_slots"] = slots[j]
                    new["eplb_nrep"] = nreps[j]
                    layers.append(new)
                    j += 1
                return {**params, "layers": layers}

            self._mh_eplb_apply = jax.jit(
                eplb_apply_all, donate_argnums=(0,)
            )
            ops.register(
                "eplb_apply", self._mh_eplb_apply,
                state_in={0: "params"}, state_out={0: "params"},
            )
        if self.guided_enabled:
            # guided-table sync: by-value incremental updates (the [B] mask
            # on admission/release, one slot's rows on a guided admission)
            # that BOTH sides store back — decode dispatches then reference
            # the tables as state, never re-broadcasting them
            grepl = NamedSharding(self.mesh, P())

            def guided_active(a):
                return jnp.asarray(a)

            def guided_row(gc, gt, crow, trow, slot):
                return gc.at[slot].set(crow), gt.at[slot].set(trow)

            self._mh_guided_active = jax.jit(
                guided_active, out_shardings=grepl
            )
            self._mh_guided_row = jax.jit(guided_row, donate_argnums=(0, 1))
            ops.register(
                "guided_active", self._mh_guided_active,
                state_in={}, state_out={0: "g_active_dev"},
            )
            ops.register(
                "guided_row", self._mh_guided_row,
                state_in={0: "g_class_dev", 1: "g_trans_dev"},
                state_out={0: "g_class_dev", 1: "g_trans_dev"},
            )
        ops.register(
            "reset_slot", self._reset_slot_fn,
            state_in={0: "pmasks", 1: "counts"},
            state_out={0: "pmasks", 1: "counts"},
        )
        ops.register("embed", self._embed_fn, state_in={0: "params"}, state_out={})
        if self.cfg.spec_draft is not None:
            # speculative decoding: the spec horizon's carry shares names
            # with decode_multi's, so spec and normal horizons chain on each
            # other across the replay table exactly as in-process
            ops.register(
                "spec_multi", self._spec_multi_fn,
                state_in={0: "params", 1: "draft_params", 2: "k", 3: "v",
                          4: "dk", 5: "dv", 11: "lora"},
                state_out={0: "k", 1: "v", 2: "dk", 3: "dv",
                           5: "carry_tokens", 6: "carry_seq_lens",
                           7: "carry_steps"},
                carry_in={6: "carry_tokens", 7: "carry_seq_lens",
                          10: "carry_steps"},
            )
            ops.register(
                "draft_prefill", self._draft_prefill_fn,
                state_in={0: "draft_params", 1: "dk", 2: "dv"},
                state_out={0: "dk", 1: "dv"},
            )
        if getattr(self, "_embed_chunk_fn", None) is not None:
            ops.register(
                "embed_chunk", self._embed_chunk_fn,
                state_in={0: "params", 1: "k", 2: "v"},
                state_out={0: "k", 1: "v"},
            )

        # KV transfer legs for disaggregation across a multihost group: the
        # gather REPLICATES its output over the mesh (a collective all-gather
        # of the tp shards) so the leader can read the page bytes host-side;
        # the scatter is a replayed collective taking pages by value.
        repl = NamedSharding(self.mesh, P())

        def kv_gather(k_caches, v_caches, ids):
            k = jnp.stack([kc[ids] for kc in k_caches])  # [L, n, bs, kvh, d]
            v = jnp.stack([vc[ids] for vc in v_caches])
            return k, v

        def kv_scatter(k_caches, v_caches, kp, vp, ids):
            new_k = [
                kc.at[ids].set(kp[i].astype(kc.dtype))
                for i, kc in enumerate(k_caches)
            ]
            new_v = [
                vc.at[ids].set(vp[i].astype(vc.dtype))
                for i, vc in enumerate(v_caches)
            ]
            return new_k, new_v

        self._mh_kv_gather = jax.jit(kv_gather, out_shardings=(repl, repl))
        self._mh_kv_scatter = jax.jit(kv_scatter)
        ops.register(
            "kv_gather", self._mh_kv_gather,
            state_in={0: "k", 1: "v"}, state_out={},
        )
        ops.register(
            "kv_scatter", self._mh_kv_scatter,
            state_in={0: "k", 1: "v"}, state_out={0: "k", 1: "v"},
        )
        self._mh_ops = ops
        if self._mh.is_leader:
            self._prefill_fn = ops.leader_fn("prefill")
            self._decode_fn = ops.leader_fn("decode")
            self._decode_multi_fn = ops.leader_fn("decode_multi")
            self._reset_slot_fn = ops.leader_fn("reset_slot")
            self._embed_fn = ops.leader_fn("embed")
            if self.cfg.spec_draft is not None:
                self._spec_multi_fn = ops.leader_fn("spec_multi")
                self._draft_prefill_fn = ops.leader_fn("draft_prefill")
            if self.guided_enabled:
                self._mh_guided_active = ops.leader_fn("guided_active")
                self._mh_guided_row = ops.leader_fn("guided_row")
            if self._eplb_enabled:
                self._mh_eplb_apply = ops.leader_fn("eplb_apply")
            if getattr(self, "_embed_chunk_fn", None) is not None:
                self._embed_chunk_fn = ops.leader_fn("embed_chunk")
            self._mh_kv_gather = ops.leader_fn("kv_gather")
            self._mh_kv_scatter = ops.leader_fn("kv_scatter")

    def follow(self) -> None:
        """Follower process body: replay leader dispatches until stop/EOF.

        The reference's analog is a non-leader TP rank blocking inside the
        engine's collective step loop (components/src/dynamo/vllm/main.py:67);
        here the loop is explicit because each JAX process must issue the
        same XLA programs itself.
        """
        assert self._mh is not None and not self._mh.is_leader
        self._mh_ops.follow()

    # ---------------------------------------------------------------- serving
    async def generate(
        self, request: Any, context: Context
    ) -> AsyncIterator[BackendOutput]:
        # ``submit`` (engine/telemetry.py): this thread is the step loop's too,
        # and what it does here it does inside the loop's yield / idle / awaits
        with submit_span(self) as sub:
            req = request if isinstance(request, PreprocessedRequest) else (
                PreprocessedRequest.from_obj(request)
            )
            sub.request_id = req.request_id
            n_prompt = len(req.token_ids) + len(req.prior_token_ids)
            if n_prompt >= self.cfg.max_context:
                raise ValueError(
                    f"prompt {n_prompt} tokens exceeds engine max_context "
                    f"{self.cfg.max_context}"
                )
            pages = n_prompt // self.cfg.block_size + 1
            if self._ring is not None:
                pages = min(pages, self._ring.pages)  # a whole ring at most
            if pages + 1 > self.cfg.num_blocks:
                # would wait forever in admission — no amount of eviction frees
                # enough pages for this prompt
                raise ContextLengthError(
                    f"prompt {n_prompt} tokens cannot fit the KV pool "
                    f"({self.cfg.num_blocks} blocks x {self.cfg.block_size})"
                )
            for grp in self._win_groups:
                # a windowed group: its table's pages at most, of its own pool
                if min(pages, grp.pages) + 1 > grp.allocator.num_blocks:
                    raise ContextLengthError(
                        f"prompt {n_prompt} tokens cannot fit the pool of the "
                        f"page group of layers {list(grp.layers)} "
                        f"({grp.allocator.num_blocks} blocks x "
                        f"{self.cfg.block_size}, a window of {grp.window})"
                    )
            wanted_procs = req.annotations.get("logits_processors") or []
            if wanted_procs:
                known = {n for n, _ in self.cfg.logits_processors}
                bad = [n for n in wanted_procs if n not in known]
                if bad:
                    raise InvalidRequestError(f"unknown logits processors {bad!r}")
            lora_name = req.annotations.get("lora")
            if lora_name:
                if self.lora is None:
                    raise InvalidRequestError("engine built without LoRA support")
                if self.lora.slot_of(lora_name) == 0:
                    raise InvalidRequestError(f"unknown LoRA adapter {lora_name!r}")
            guided_tables = None
            if req.sampling.guided is not None:
                if not self.guided_enabled:
                    # soft specs (derived, e.g. from a forced tool_choice —
                    # llm/preprocessor.py) degrade to unconstrained sampling;
                    # explicit guided_* options fail loudly
                    if not req.sampling.guided.get("soft"):
                        raise GuidedRejectedError(
                            "engine built without guided decoding "
                            "(guided_max_states=0)"
                        )
                else:
                    try:
                        guided_tables = await sub.away(self._compile_guided(
                            req.sampling.guided
                        ))
                    except ValueError:
                        if not req.sampling.guided.get("soft"):
                            raise
                        # reference behavior: a failed tool-choice derivation
                        # logs and serves unconstrained (common_ext.rs:190)
                        log.warning(
                            "soft guided grammar rejected; serving unconstrained"
                        )
            if req.annotations.get("op") == "embed":
                loop = asyncio.get_event_loop()
                block_ids: Optional[List[int]] = None
                S = len(req.token_ids)
                if S > self.cfg.prefill_chunk:
                    # long input: temporary pages for the chunked pooled forward
                    # (allocated here on the loop thread — the allocator is
                    # single-threaded; never committed, released below)
                    need = (S + self.cfg.block_size - 1) // self.cfg.block_size
                    if not self.allocator.can_allocate(need):
                        raise ValueError(
                            f"no KV capacity for a {S}-token embedding "
                            f"({need} blocks needed); retry later"
                        )
                    block_ids = self.allocator.allocate(need)
                try:
                    vec = await sub.away(loop.run_in_executor(
                        self._executor, self._run_embed, list(req.token_ids),
                        block_ids,
                    ))
                finally:
                    if block_ids is not None:
                        self.allocator.release(block_ids)
                sub.close()  # no span across the caller's turn
                yield BackendOutput(
                    finish_reason=FINISH_STOP,
                    annotations={
                        "embedding": [float(v) for v in vec],
                        "input_tokens": len(req.token_ids),
                    },
                )
                return
            self._ensure_loop()
            if req.annotations.get("images"):
                if self.cfg.vision is None:
                    raise InvalidRequestError("engine built without a vision tower")
            all_tokens = req.token_ids
            if req.prior_token_ids:
                all_tokens = [*all_tokens, *req.prior_token_ids]
            st = _Seq(
                req=req,
                context=context,
                out_queue=asyncio.Queue(),
                # the sequence keeps its own flat copy: this is the one copy
                # of the prompt, and its block hashes are one pass over it
                seq=TokenBlockSequence(all_tokens, self.cfg.block_size),
                last_token=all_tokens[-1] if all_tokens else 0,
                guided_tables=guided_tables,
                # a block hash restores pages and no recurrent state: such a
                # family declines prefix hits and publishes no block
                no_cache=not self._prefix_reusable,
            )
            if guided_tables is not None and req.prior_token_ids:
                # disagg decode hop / migration resume: tokens generated so far
                # (on the prefill worker / the dead worker) already consumed
                # grammar transitions — seed the FSM past them instead of
                # restarting at 0 (which would let the grammar accept a fresh
                # full match appended to the prior output)
                try:
                    st.guided_state = guided_tables.walk(
                        0, [int(t) for t in req.prior_token_ids]
                    )
                except ValueError as e:
                    raise GuidedRejectedError(
                        f"prior tokens violate the guided grammar: {e}"
                    ) from e
            if self.cfg.spec_draft is not None:
                s = req.sampling
                st.spec_ok = (
                    s.temperature == 0.0
                    and s.logprobs == 0
                    and s.presence_penalty == 0.0
                    and s.frequency_penalty == 0.0
                    and s.repetition_penalty == 1.0
                    and not wanted_procs
                    and guided_tables is None
                )
            if req.annotations.get("images"):
                loop_mm = asyncio.get_event_loop()
                st.mm_embeds, st.mm_mask = await sub.away(
                    loop_mm.run_in_executor(
                        self._executor, self._encode_images, req
                    )
                )
                # prior_token_ids (migration replay / disagg decode hop) extend
                # the prompt past token_ids: pad the override arrays to the full
                # prefill length (generated text is never an image span)
                extra = len(all_tokens) - len(st.mm_mask)
                if extra > 0:
                    st.mm_embeds = np.concatenate(
                        [st.mm_embeds,
                         np.zeros((extra, st.mm_embeds.shape[1]), np.float32)]
                    )
                    st.mm_mask = np.concatenate(
                        [st.mm_mask, np.zeros(extra, bool)]
                    )
                # placeholder ids hash identically across different images:
                # never match or publish this prompt's blocks. (A future
                # refinement: salt the block hashes with each image's content
                # hash at its placeholder run, making mm prefixes cacheable
                # instead of uncacheable.)
                st.no_cache = True
            # disaggregated decode: pull the prefill worker's KV pages first so
            # admission sees them as a cached prefix (no recompute)
            flight = get_flight_recorder()
            kv_plan = req.kv_transfer
            if (kv_plan and kv_plan.get("tier")
                    and getattr(self, "kv_directory", None) is not None
                    and kv_plan.get("holder") == self.kv_directory.holder):
                # the planner picked us as the peer: our own G2/G3 already holds
                # these blocks, and the kvbm onboard below imports them without
                # a loopback wire copy. Drop the plan instead of self-fetching.
                kv_plan = None
            if kv_plan and kv_plan.get("address"):
                # global-directory plan (tier=True): pull from the peer's KVBM
                # G2/G3 tiers instead of its device cache. The fetch holds a
                # directory fetch lease that MUST be discharged on every path
                # (RESOURCE-LEAK "fetch-lease"): commit on any import, abort on
                # zero progress or failure — abort IS the recompute fallback,
                # never a stuck request.
                is_tier = bool(kv_plan.get("tier"))
                fetch_lease = (
                    self.kv_directory.begin_fetch(
                        kv_plan.get("holder", ""),
                        [int(h) for h in kv_plan.get("hashes", [])],
                    )
                    if is_tier and self.kv_directory is not None else None
                )
                # the fetch lifecycle lands on the request's timeline (PR 16
                # gap): started/committed/aborted bracket the wire pull, so the
                # attribution plane charges this wait to kv_fetch and a stuck
                # fetch is visible as started-without-terminal
                flight.record(
                    req.request_id, "fetch_started",
                    holder=kv_plan.get("holder", ""), tier=is_tier,
                    blocks=len(kv_plan.get("hashes", [])),
                )
                try:
                    got = await sub.away(
                        self._get_transfer_client().fetch_and_import(
                            kv_plan["address"],
                            [int(h) for h in kv_plan.get("hashes", [])],
                            traceparent=req.annotations.get("traceparent"),
                            stream=bool(kv_plan.get("stream")),
                            tier=is_tier,
                        )
                    )
                    if fetch_lease is not None:
                        if got > 0:
                            self.kv_directory.commit_fetch(fetch_lease, got)
                        else:
                            self.kv_directory.abort_fetch(fetch_lease)
                    if got > 0:
                        flight.record(
                            req.request_id, "fetch_committed", tokens=got,
                        )
                    else:
                        flight.record(
                            req.request_id, "fetch_aborted",
                            reason="zero_progress",
                        )
                    log.debug("imported %d transferred kv tokens for %s", got, req.request_id[:8])
                    flight.record(
                        req.request_id, "transfer",
                        tokens=got, address=kv_plan["address"],
                    )
                except Exception as e:
                    if fetch_lease is not None:
                        self.kv_directory.abort_fetch(fetch_lease)
                    log.exception("kv transfer failed; recomputing prefill locally")
                    flight.record(
                        req.request_id, "fetch_aborted", reason=str(e)[:200],
                    )
                    flight.record(
                        req.request_id, "transfer",
                        tokens=0, error=str(e)[:200],
                        address=kv_plan["address"],
                    )
            if self.kvbm is not None:
                try:
                    await sub.away(self._onboard_from_kvbm(st))
                except Exception:
                    log.exception("kvbm onboard failed; prefilling from scratch")
            # disaggregated prefill: announce our pages on the way out
            is_prefill_side = req.annotations.get("disagg") == "prefill"
            st.sla = spec_from_annotations(req.annotations)
            st.t_queued = now_ns()
            queued_fields: Dict[str, Any] = dict(
                prompt_tokens=n_prompt, waiting=len(self._waiting),
                # what this thread spent on the request so far, the step
                # loop waiting: the request's ``submit`` spans up to here
                submit_ms=round(sub.held_ms(), 3),
            )
            if st.sla is not None:
                # the queued event carries the promise so /debug/requests?id=
                # can compute the budget breakdown (runtime/slo.py) at read time
                queued_fields.update(
                    sla_class=st.sla.sla_class,
                    ttft_target_s=st.sla.ttft_target_s,
                    itl_target_s=st.sla.itl_target_s,
                    deadline_s=st.sla.deadline_s,
                )
            flight.record(req.request_id, "queued", **queued_fields)
            self._waiting.append(st)
            self._wake.set()
        while True:
            item = await st.out_queue.get()
            if item is None:
                return
            # ``deliver``: from here to the caller asking for the next item,
            # or closing the generator at the yield
            t_got = now_ns()
            try:
                if (
                    is_prefill_side
                    and item.finish_reason is not None
                    and self.transfer_address is not None
                    and not st.no_cache
                ):
                    prompt_blocks = len(req.token_ids) // self.cfg.block_size
                    item.kv_transfer = {
                        "address": self.transfer_address,
                        "hashes": [int(h) for h in st.seq.sequence_hashes()[:prompt_blocks]],
                        "num_tokens": prompt_blocks * self.cfg.block_size,
                        # this server speaks the block-window streaming protocol
                        "stream": True,
                    }
                if item.finish_reason is not None:
                    # observability BEFORE the final yield: consumers typically
                    # return at the finish frame, which closes this generator at
                    # the yield (code after it would never run)
                    self._request_finished(st, item.finish_reason)
                yield item
            finally:
                record_request_span(self, "deliver", t_got, req.request_id)
            if item.finish_reason is not None:
                return

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.create_task(self._loop())

    def stop(self) -> None:
        if self._loop_task is not None:
            self._loop_task.cancel()
        if getattr(self, "kv_directory", None) is not None:
            # drained worker checkpointing out: revoke the directory lease so
            # every advertisement withdraws in one call (peers stop planning
            # fetches against a worker that is gone). Async close rides the
            # running loop; with no loop, store-lease TTL expiry does it.
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                loop = None
            if loop is not None:
                spawn_bg(self.kv_directory.close())
        if self._transfer_server is not None:
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                loop = None  # no running loop (sync teardown): sockets close with us
            if loop is not None:
                # spawn_bg pins the task (the loop only weak-refs it) and
                # logs a failed stop; nothing joins it — stop() is the
                # shutdown path itself
                spawn_bg(self._transfer_server.stop(0.5))
        if getattr(self, "_kv_transfer_srv", None) is not None:
            self._kv_transfer_srv.close()
            if self.transfer_address is not None:
                from .transfer import LOCAL_SERVERS

                LOCAL_SERVERS.pop(self.transfer_address, None)
        self._executor.shutdown(wait=False)
        self._fetch_executor.shutdown(wait=False)
        if self._prep is not None:
            self._prep.stop()
        if self._mh is not None and self._mh.is_leader:
            # broadcasts __stop__ under the dispatch lock so an in-flight
            # dispatch can't slip a collective past the followers' exit
            self._mh_ops.close()

    # ---------------------------------------------------------------- EPLB
    @property
    def _eplb_enabled(self) -> bool:
        # a field of MoeConfig alone
        return getattr(self.mcfg, "redundant_experts", 0) > 0

    def measure_expert_load(self, token_ids: List[int]) -> np.ndarray:
        """[num_layers, E] tokens-per-logical-expert for a probe batch
        (models/eplb.py probe — dense forward, OFF the serving hot path;
        the reference collects the same statistic from its engines
        periodically). Call from the profiler / an ops endpoint with
        representative prompts, feed the summed counts to
        eplb_rebalance."""
        from ..models import eplb as eplb_mod

        if not self._eplb_enabled:
            raise ValueError("engine model has no EPLB (redundant_experts=0)")
        if self._mh is not None:
            raise ValueError(
                "the load probe is not in the multihost replay table; feed "
                "externally collected counts to eplb_rebalance instead"
            )
        if self._probe_load_fn is None:
            self._probe_load_fn = jax.jit(
                partial(eplb_mod.probe_expert_load, cfg=self.mcfg)
            )
        toks = jnp.asarray(np.asarray(token_ids, np.int32))
        pos = jnp.arange(len(token_ids), dtype=jnp.int32)
        return np.asarray(
            self._probe_load_fn(self.params, token_ids=toks, positions=pos)
        )

    def eplb_rebalance(self, counts: np.ndarray) -> Dict[str, Any]:
        """Re-plan the redundant-expert replicas from measured counts and
        swap the plan into the live params — table updates + a weight
        gather along the (sharded) expert dim, zero recompiles (the slot
        count is static). ``counts``: [E] aggregated, or [L, E] per layer.
        Output tokens are unchanged by construction (replicas carry the
        logical weights; only the load placement moves)."""
        from ..models import eplb as eplb_mod

        if not self._eplb_enabled:
            raise ValueError("engine model has no EPLB (redundant_experts=0)")
        counts = np.asarray(counts, np.float64)
        per_layer = counts.ndim == 2
        ep = meshlib.tp_size(self.mesh)
        E, R = self.mcfg.num_experts, self.mcfg.redundant_experts
        moe_layers = [
            i for i, lp in enumerate(self.params["layers"])
            if "eplb_slots" in lp
        ]
        # validate BEFORE mutating anything: a wrong-length counts vector
        # must not silently broadcast into a do-nothing plan or fail after
        # some layers were already swapped
        if per_layer:
            if counts.shape != (len(moe_layers), E):
                raise ValueError(
                    f"counts shape {counts.shape} != "
                    f"({len(moe_layers)} moe layers, {E} experts)"
                )
        elif counts.shape != (E,):
            raise ValueError(
                f"counts shape {counts.shape} != ({E} experts,)"
            )
        plans = [
            eplb_mod.plan(counts[n] if per_layer else counts, E, R, ep=ep)
            for n in range(len(moe_layers))
        ]

        def _apply() -> None:
            if self._mh is not None:
                # one replayed op applies every layer's plan: followers swap
                # their params handle in lockstep (state_out), shardings
                # pinned inside the jitted update
                self.params = self._mh_eplb_apply(
                    self.params,
                    np.stack([p.phys_src for p in plans]),
                    np.stack([p.slots for p in plans]),
                    np.stack([p.nrep for p in plans]),
                )
            else:
                for n, i in enumerate(moe_layers):
                    self.params["layers"][i] = eplb_mod.apply_plan(
                        self.params["layers"][i], plans[n]
                    )

        # the swap MUST run on the step executor: decode/prefill dispatches
        # read self.params on that (single) thread, and the multihost op
        # DONATES the old buffers — a swap racing an in-flight dispatch
        # would hand it deleted arrays (or, multihost, a stale handle the
        # followers no longer hold)
        self._executor.submit(_apply).result()
        return {
            "layers": len(plans),
            "redundant_experts": R,
            "max_shard_load": (
                plans[0].max_shard_load(
                    counts[0] if per_layer else counts, ep
                ) if plans else None
            ),
        }

    # ------------------------------------------------------- kvbm offload/onboard
    def _enqueue_offload_gather(self, pending: List[Tuple[int, int]]):
        """Event-loop thread: ENQUEUE the device-side page gathers for sealed
        blocks immediately (cheap async dispatch). Enqueue order is what
        guarantees the gather reads the pages before any later-dispatched
        decode/prefill can rewrite them after LRU eviction — the host fetch
        itself can then run lazily on the offload thread."""
        from ..ops import block_copy as bc

        ids = jnp.asarray(np.asarray([bid for bid, _, _ in pending], np.int32))
        gathered = []
        for kc, vc in zip(self.k_caches, self.v_caches):
            if self.kv_quantized:
                # payload + scale pages move as one unit (ops/block_copy)
                gathered.append((
                    bc.gather_blocks_quant(kc, ids),
                    bc.gather_blocks_quant(vc, ids),
                ))
            else:
                gathered.append((kc[ids], vc[ids]))  # [n, bs, kvh, d] each
        return gathered

    def _offload_fetch(self, pending: List[Tuple[int, int, int]], gathered) -> None:
        """Offload thread: fetch the already-gathered pages and hand them to
        the kvbm priority queue (prefix blocks outrank decode blocks; the
        kvbm worker does the tier writes). Best-effort: failures are logged,
        never fatal.

        Tier bytes are the STORAGE format (kvbm/layout.block_shape_for):
        model dtype for float caches — a bf16 model stores bf16 blocks, not
        2x-inflated float32 — and the flat int8+scales codec buffer for
        kv_dtype=int8 (bit-exact round trip, no float detour)."""
        t_offload = time.time_ns()
        offloaded_bytes = 0
        try:
            if self.kv_quantized:
                codec = self._kv_codec()
                n = len(pending)
                pay = np.empty((n,) + codec.payload_shape, np.int8)
                scl = np.empty((n,) + codec.scales_shape, np.float32)
                for li, (kq, vq) in enumerate(gathered):
                    pay[:, li, 0] = np.asarray(kq.data)
                    pay[:, li, 1] = np.asarray(vq.data)
                    scl[:, li, 0] = np.asarray(kq.scale)
                    scl[:, li, 1] = np.asarray(vq.scale)
                for i, (_, h, prio) in enumerate(pending):
                    self.kvbm.offload(
                        h, codec.encode(pay[i], scl[i]), priority=prio
                    )
                offloaded_bytes = len(pending) * codec.nbytes
                return
            store_dtype = np.dtype(self.mcfg.dtype)
            layers = []
            for k_dev, v_dev in gathered:
                k = np.asarray(k_dev, store_dtype)
                v = np.asarray(v_dev, store_dtype)
                layers.append(np.stack([k, v], axis=1))  # [n, 2, bs, kvh, d]
            arr = np.stack(layers, axis=1)               # [n, L, 2, bs, kvh, d]
            for i, (_, h, prio) in enumerate(pending):
                # copy: a view of arr would pin the whole n-block gather
                # buffer in the host tier for as long as one block lives
                self.kvbm.offload(h, arr[i].copy(), priority=prio)
            offloaded_bytes = int(arr.nbytes)
        except Exception:
            log.exception("kv offload failed (continuing without write-through)")
        finally:
            tracer = get_tracer()
            if tracer.enabled and offloaded_bytes:
                # background batch spanning many requests: its own trace,
                # not parented to any one request
                tracer.emit(
                    "kvbm.offload", t_offload, time.time_ns(),
                    blocks=len(pending), bytes=offloaded_bytes,
                )

    def _kv_codec(self):
        """The int8 block codec shared by the KVBM tiers and the native
        transfer arena (kvbm/layout.QuantizedBlockCodec)."""
        from ..kvbm.layout import QuantizedBlockCodec, block_shape_for

        codec = getattr(self, "_kv_codec_cached", None)
        if codec is None:
            codec = self._kv_codec_cached = QuantizedBlockCodec(
                block_shape_for(self.mcfg, self.cfg.block_size, "int8")
            )
        return codec

    def _scatter_blocks(self, local_ids: List[int], arr) -> None:
        """Executor thread: device scatter only — no allocator access here
        (the allocator is single-threaded on the event loop).

        ``arr`` is either float pages [n, L, 2, bs, kvh, d] or, for int8
        caches, a (payload int8 [n, L, 2, bs, kvh, d], scales f32
        [n, L, 2, kvh]) pair that scatters straight into the quantized cache
        — no float detour, bit-exact. Float pages arriving at a quantized
        cache (a float-cache transfer peer) quantize on the way in."""
        if isinstance(arr, tuple) and not self.kv_quantized:
            # quantized pages arriving at a float cache: dequantize
            # host-side BEFORE any branch — the multihost scatter below
            # (multihost engines are always float; int8+mh is gated at
            # construction) must see plain pages too
            from ..ops.quant import dequantize_blocks_np

            arr = dequantize_blocks_np(arr[0], arr[1])
        if self._mh is not None:
            # arr [n, L, 2, ...] -> kp/vp [L, n, ...] by value: the scatter
            # is a replayed collective (eager .at[].set on a mesh spanning
            # processes would be a leader-only dispatch and hang the group)
            kp = np.ascontiguousarray(np.moveaxis(arr[:, :, 0], 0, 1))
            vp = np.ascontiguousarray(np.moveaxis(arr[:, :, 1], 0, 1))
            self.k_caches, self.v_caches = self._mh_kv_scatter(
                self.k_caches, self.v_caches, kp, vp,
                np.asarray(local_ids, np.int32),
            )
            return
        ids = jnp.asarray(np.asarray(local_ids, np.int32))
        if self.kv_quantized:
            from ..ops import block_copy as bc
            from ..ops.quant import QuantizedKV, quantize_blocks_np

            if isinstance(arr, tuple):
                payload, scales = arr
            else:
                payload, scales = quantize_blocks_np(np.asarray(arr))
            for li in range(payload.shape[1]):
                self.k_caches[li] = bc.scatter_blocks_quant(
                    self.k_caches[li], ids,
                    QuantizedKV(
                        jnp.asarray(payload[:, li, 0]),
                        jnp.asarray(np.ascontiguousarray(scales[:, li, 0])),
                    ),
                )
                self.v_caches[li] = bc.scatter_blocks_quant(
                    self.v_caches[li], ids,
                    QuantizedKV(
                        jnp.asarray(payload[:, li, 1]),
                        jnp.asarray(np.ascontiguousarray(scales[:, li, 1])),
                    ),
                )
            return
        dtype = self.mcfg.dtype
        for li in range(arr.shape[1]):
            k = jnp.asarray(arr[:, li, 0], dtype)
            v = jnp.asarray(arr[:, li, 1], dtype)
            self.k_caches[li] = self.k_caches[li].at[ids].set(k)
            self.v_caches[li] = self.v_caches[li].at[ids].set(v)

    async def import_blocks(self, hashes: List[int], arr) -> int:
        """Import [n, L, 2, bs, kvh, d] pages (or an int8 (payload, scales)
        pair — see _scatter_blocks) as content-addressed cached pages.
        Shared by the kv transfer plane and kvbm onboarding. Allocator
        mutations stay on the event-loop thread; only the scatter runs in
        the executor."""
        n = (arr[0] if isinstance(arr, tuple) else arr).shape[0]
        try:
            local_ids = self.allocator.allocate(n)
        except OutOfBlocks:
            log.warning("no room to import %d blocks; skipping", n)
            return 0
        loop = asyncio.get_event_loop()
        try:
            await loop.run_in_executor(self._executor, self._scatter_blocks, local_ids, arr)
        except Exception:
            self.allocator.release(local_ids)
            raise
        for bid, h in zip(local_ids, hashes):
            self.allocator.commit(bid, h)
        self.allocator.release(local_ids)
        if n and self.kv_commits is not None:
            self.kv_commits.fire()
        return n

    async def _onboard_from_kvbm(self, st: "_Seq") -> None:
        """Pull a host/disk-cached prefix into device pages before admission."""
        if self.kvbm is None:
            return
        t_onboard = time.time_ns()
        bs = self.cfg.block_size
        hashes = st.seq.sequence_hashes()[: (len(st.seq) - 1) // bs]
        have = len(self.allocator.match_prefix(hashes))
        loop = asyncio.get_event_loop()
        # match_prefix can hit the G4 remote store (blocking socket): keep it
        # off the event loop, same as the load below
        n = await loop.run_in_executor(
            None, self.kvbm.match_prefix, hashes[have:]
        )
        if n == 0:
            return
        arr = await loop.run_in_executor(None, self.kvbm.load_prefix, hashes[have : have + n])
        if arr is None:
            return
        # format guard: disk/remote tiers survive restarts and are shared
        # fleet-wide, so blobs written under a DIFFERENT kv_dtype (or model
        # shape) can come back under the same content hashes — treat them as
        # a miss and recompute rather than crash the loop or import garbage
        if self.kv_quantized:
            codec = self._kv_codec()
            if (
                arr.dtype != np.uint8 or arr.ndim != 2
                or arr.shape[1] != codec.nbytes
            ):
                log.warning(
                    "kvbm blocks are not this engine's int8 codec format "
                    "(%s %s); skipping onboard — clear stale tiers via "
                    "/clear_kv_blocks", arr.dtype, arr.shape,
                )
                return
            # decode the flat int8+scales buffers to the (payload, scales)
            # pair the quantized scatter takes — the round trip never
            # touches floats, so onboarded blocks are bit-equal to what
            # was offloaded
            arr = codec.decode_many(arr)
        else:
            expect = (
                self.mcfg.num_layers, 2, self.cfg.block_size,
                self.mcfg.num_kv_heads, self.mcfg.head_dim,
            )
            if arr.ndim != 6 or arr.shape[1:] != expect:
                log.warning(
                    "kvbm blocks do not match this engine's KV layout "
                    "(%s vs %s); skipping onboard", arr.shape[1:], expect,
                )
                return
        got = await self.import_blocks(list(hashes[have : have + n]), arr)
        if got:
            log.debug("onboarded %d blocks from kvbm for %s", got, st.req.request_id[:8])
            get_flight_recorder().record(
                st.req.request_id, "onboard",
                blocks=got, tokens=got * bs,
            )
            tracer = get_tracer()
            if tracer.enabled:
                # int8 tiers decode to a (payload, scales) pair above
                nbytes = (
                    sum(int(a[:got].nbytes) for a in arr)
                    if isinstance(arr, tuple) else int(arr[:got].nbytes)
                )
                tracer.emit(
                    "kvbm.onboard", t_onboard, time.time_ns(),
                    traceparent=st.req.annotations.get("traceparent"),
                    request_id=st.req.request_id,
                    blocks=got, bytes=nbytes,
                )

    # ------------------------------------------------------------- step loop
    def _unix_ns(self, t_ns: int) -> int:
        """A stamp of the loop's clock (``telemetry.now_ns``) as unix ns, for
        the sinks that carry wall time (OTLP spans, the SLO ledger)."""
        return t_ns + self._wall_offset_ns

    async def _loop(self) -> None:
        loop = asyncio.get_event_loop()
        self._wall_offset_ns = time.time_ns() - now_ns()
        try:
            while True:
                if not self._waiting and all(s is None for s in self._slots):
                    with loop_span(self, "idle"):
                        self._chains.clear()  # all snapshot seqs are done by now
                        self._wake.clear()
                        await self._wake.wait()
                with loop_span(self, "admit"):
                    # chaos drill hook: an armed engine.step fault crashes
                    # the loop through the real crash path below (error
                    # finishes, watchdog dereg, migration replay) — no-op
                    # unarmed
                    await FAULTS.ainject("engine.step")
                    self._admit_cancelled()
                    self._try_admit()
                # chunked prefill: ONE bounded chunk per tick, so running
                # decodes keep making progress under a long prefill; round-
                # robin across prefilling sequences so a short prompt is not
                # starved behind a long one
                did_mixed = False
                mixed_blocked = False
                pick = mixed_seqs = None
                at_once = False
                with loop_span(self, "book"):
                    # a mixed step launched last tick and not read yet, alone
                    # in flight: THIS tick's program is launched on its carry
                    # before its results are read (below). Inside the span:
                    # rebinding ``link`` lets go of the link of the tick
                    # before, and freeing its device arrays takes 40-150 us
                    link = (
                        self._chains[0]
                        if len(self._chains) == 1 and self._chains[0].mixed
                        else None
                    )
                    prefilling = [
                        s for s in self._slots
                        if s is not None and not s.done and not s.prefilled
                        and not s.prefill_inflight
                    ]
                    if prefilling:
                        pick = prefilling[self._prefill_rr % len(prefilling)]
                        self._prefill_rr += 1
                    if pick is not None and pick.context.is_stopped():
                        # client gone mid-prefill: stop burning chunks, free
                        # the slot at the next reap
                        pick.done = True
                        pick.out_queue.put_nowait(BackendOutput(
                            finish_reason="cancelled",
                            cumulative_tokens=pick.produced,
                        ))
                        pick = None
                    elif pick is not None and not self._slide_chunk(pick):
                        # a windowed group's pool cannot give the chunk's
                        # pages now: its turn stays, the rows that decode
                        # (or finish) let pages go
                        pick = None
                        self._prefill_rr -= 1
                    elif pick is not None:
                        if pick.t_prefill_start == 0:
                            pick.t_prefill_start = now_ns()
                        chunk_from = pick.prefill_pos
                        # mixed continuous batching: when decode rows are
                        # resident the chunk rides along with ONE decode
                        # step in a single program — decode never stalls
                        # behind the prefill. Horizons in flight drain
                        # first (the host does not know a row's length
                        # through a horizon it has not read); a mixed link
                        # in flight does not: what it advanced is one token
                        # a row, which stays on the device as this step's
                        # input (_decode_dispatch_arrays)
                        if self.mixed_enabled and (
                            not self._chains or link is not None
                        ):
                            snap = self._decode_snapshot(link)
                            if any(s is not None for s in snap):
                                at_once = self._reads_at_once(snap)
                                if link is not None and at_once:
                                    # the host's view of these rows has to
                                    # be whole: the link is read first
                                    pick = None
                                elif self._prepare_mixed(snap, link):
                                    mixed_seqs = snap
                                elif link is not None:
                                    # no room past the token in flight:
                                    # read the link, book from what it left
                                    pick = None
                                else:
                                    # booking failed (block pressure /
                                    # context headroom): this prefill runs
                                    # split, and horizons must keep
                                    # pipelining rather than wait for a
                                    # fused step that cannot book
                                    mixed_blocked = True
                                if pick is None:
                                    self._prefill_rr -= 1  # its turn stays
                if pick is not None:
                    t_step = time.perf_counter()
                    with loop_span(self, "step"):
                        if mixed_seqs is not None:
                            chain, res = await loop.run_in_executor(
                                self._executor, self._run_mixed_step, pick,
                                mixed_seqs, link, at_once,
                            )
                            self._chains.append(chain)
                            did_mixed = True
                        else:
                            res = await loop.run_in_executor(
                                self._executor, self._run_prefill_chunk, pick
                            )
                    with loop_span(self, "emit"):
                        # the chunk's step is launched: program order on the
                        # device is what later readers of its pages depend on
                        self._commit_prefilled_blocks(pick)
                        if res is not None:
                            fut = self._fetch_executor.submit(
                                self._fetch_prefill_result, *res
                            )
                            task = asyncio.ensure_future(
                                self._finish_prefill(res[0], fut, res[-1])
                            )
                            self._prefill_tasks.add(task)
                            task.add_done_callback(self._prefill_tasks.discard)
                        if mixed_seqs is None:
                            self._step_stats(
                                "prefill", time.perf_counter() - t_step,
                                pick.prefill_pos - chunk_from,
                            )
                # top up the horizon pipeline BEFORE fetching the oldest
                # results: the readback overlaps the in-flight links'
                # device compute (at decode_pipeline 1 a horizon has no
                # successor: it is launched and read in one tick). Dispatch
                # runs on the executor: the first call jit-compiles (30-90s
                # cold) and must not stall the event loop's lease heartbeats.
                # while a mixed-eligible prefill is in progress, the pipeline
                # is NOT topped up: in-flight horizons drain (the fused step
                # takes no horizon's carry), and once they have, every tick
                # launches one fused chunk+decode step on the carry of the
                # one before and then reads that one, until the prefill
                # completes — decode keeps advancing, prefill keeps
                # chunking, and the device does not wait for the host's turn
                with loop_span(self, "book"):
                    has_active = any(
                        s is not None and not s.done and s.prefilled
                        for s in self._slots
                    )
                    mixed_wait = (
                        self.mixed_enabled and bool(prefilling)
                        and has_active and not mixed_blocked
                    )
                while True:
                    with loop_span(self, "book"):
                        top_up = (
                            has_active
                            and not self._waiting
                            and not did_mixed
                            and not mixed_wait
                            and self._may_top_up()
                            and (not self._chains
                                 or self._can_chain(self._chains[-1]))
                            and self._prepare_horizon()
                        )
                        if top_up:
                            prev = self._chains[-1] if self._chains else None
                            snapshot = self._decode_snapshot(
                                prev if prev is not None and prev.mixed
                                else None
                            )
                    if not top_up:
                        break
                    with loop_span(self, "step"):
                        chain = await loop.run_in_executor(
                            self._executor, self._dispatch_horizon, prev,
                            snapshot,
                        )
                        chain.fetch = self._fetch_executor.submit(
                            self._fetch_packed, chain
                        )
                        self._chains.append(chain)
                if self._chains and not (
                    did_mixed and len(self._chains) == 1 and not at_once
                ):
                    # the oldest link's results. A mixed step launched this
                    # tick with none before it stays in flight: the next
                    # tick launches on its carry first, and reads it then
                    await self._read_chain(self._chains.popleft())
                elif has_active and not did_mixed:
                    t_step = time.perf_counter()
                    with loop_span(self, "book"):
                        snapshot = self._decode_snapshot()
                    with loop_span(self, "step"):
                        results = await loop.run_in_executor(
                            self._executor, self._run_decode, snapshot
                        )
                    with loop_span(self, "emit"):
                        for rst, tok, lp, tids, tvals in results:
                            self._accept_token(rst, tok, lp, tids, tvals)
                        self._step_stats(
                            "decode", time.perf_counter() - t_step, len(results)
                        )
                elif self._prefill_tasks and not prefilling:
                    # nothing to compute until a first-token readback lands:
                    # park instead of busy-spinning through the loop (a wait
                    # for device results, like a horizon's: "fetch")
                    with loop_span(self, "fetch"):
                        self._wake.clear()
                        try:
                            await asyncio.wait_for(self._wake.wait(), 0.05)
                        except asyncio.TimeoutError:
                            pass
                with loop_span(self, "reap"):
                    self._reap_finished()
                    if self._offload_pending and self.kvbm is not None:
                        pending, self._offload_pending = self._offload_pending, []
                        # gather ENQUEUE happens here on the loop thread, in
                        # program order before any later horizon dispatch
                        # that could evict+rewrite the pages; only the host
                        # fetch is fire-and-forget (on its own thread so it
                        # never delays the decode executor)
                        gathered = self._enqueue_offload_gather(pending)
                        self._offload_executor.submit(
                            self._offload_fetch, pending, gathered
                        )
                with loop_span(self, "publish"):
                    await self._publish_events()
                with loop_span(self, "yield"):
                    await asyncio.sleep(0)
        except asyncio.CancelledError:
            pass
        except Exception as crash:
            log.exception("engine loop crashed")
            self.healthy = False
            if self.on_crash is not None:
                spawn_bg(self.on_crash(crash))
            for st in list(self._waiting) + [s for s in self._slots if s]:
                st.done = True
                evac = self._evacuation_plan(st)
                st.out_queue.put_nowait(BackendOutput(
                    finish_reason="error", cumulative_tokens=st.produced,
                    annotations={"evacuation": evac} if evac else {},
                ))
                if st.block_ids:
                    self._release(st)
            self._waiting = []
            self._slots = [None] * self.cfg.max_batch_size
            self._seq_lens[:] = 0
            self._chains.clear()

    async def _read_chain(self, chain: _Chain) -> None:
        """Loop thread: await the oldest link's results (``fetch``) and hand
        them to their requests (``emit``), then the link's StepStats."""
        if chain.mixed:
            if chain.results is None:
                with loop_span(self, "fetch"):
                    chain.results = await asyncio.wrap_future(chain.fetch)
                    self._took(chain.seq)
            with loop_span(self, "emit"):
                results, self._moe_last = chain.results
                kept = 0
                for rst, tok, lp, tids, tvals in results:
                    # ended while this link was in flight (a stop token, a
                    # cancel: what the host could not foresee), perhaps
                    # reaped: its token here is the discarded tail
                    if rst.done or self._slots[rst.slot] is not rst:
                        continue
                    kept += 1
                    self._accept_token(rst, tok, lp, tids, tvals)
                self._count_state(kept, 0, 1)
                self._step_stats(
                    "mixed", (now_ns() - chain.t0_ns) / 1e9,
                    chain.chunk_tokens + kept, link=chain,
                )
            return
        t_step = time.perf_counter()
        with loop_span(self, "fetch"):
            packed = await asyncio.wrap_future(chain.fetch)
            self._took(chain.seq)
        with loop_span(self, "emit"):
            emitted_before = sum(
                s.produced for s in chain.seqs if s is not None
            )
            self._apply_packed(chain, packed)
            self._step_stats(
                "decode", time.perf_counter() - t_step,
                sum(s.produced for s in chain.seqs if s is not None)
                - emitted_before,
            )

    def _reads_at_once(self, seqs: List[Optional["_Seq"]]) -> bool:
        """Whether a mixed step over the ``seqs`` snapshot has to be read
        before the next step can be built, from what the loop can observe:
        a guided row among its decode rows (the next dispatch resyncs the
        host's FSM states, which are walked as tokens are accepted), or a
        speculative draft (its rounds start from the host's tokens). Such a
        step is read by the executor (``sync``), and nothing is launched on
        top of one that is not."""
        if self.cfg.spec_draft is not None:
            return True
        return self.guided_enabled and any(
            st is not None and self._g_active[i] for i, st in enumerate(seqs)
        )

    def _may_top_up(self) -> bool:
        """Whether one more horizon may be launched on the chain as it
        stands: up to decode_pipeline horizons in flight, as ever. An
        unread mixed link (always the oldest) allows exactly ONE program on
        top of it, whatever decode_pipeline says, and none while the first
        token of the chunk it carried is still on its way: that row joins
        the batch from the host, so a horizon launched now would run all its
        steps without it."""
        if not self._chains:
            return True
        if self._chains[0].mixed:
            return len(self._chains) == 1 and not any(
                s is not None and s.prefill_inflight for s in self._slots
            )
        return len(self._chains) < self.cfg.decode_pipeline

    def _admit_cancelled(self) -> None:
        keep = []
        for st in self._waiting:
            if st.context.is_stopped():
                st.out_queue.put_nowait(
                    BackendOutput(finish_reason="cancelled", cumulative_tokens=0)
                )
            else:
                keep.append(st)
        self._waiting = keep

    def _free_slot(self) -> int:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return -1

    def _try_admit(self) -> List[_Seq]:
        admitted: List[_Seq] = []
        still: List[_Seq] = []
        for st in self._waiting:
            slot = self._free_slot()
            if slot < 0:
                still.append(st)
                continue
            prompt_len = len(st.seq)
            hashes = st.seq.sequence_hashes()
            # reuse at most the blocks strictly before the last prompt token so
            # prefill always has >=1 token to produce logits from
            reusable = min(len(hashes), (prompt_len - 1) // self.cfg.block_size)
            if st.no_cache:
                reusable = 0
            if self._win_groups:
                # pages by layer kind: a hit is as long as EVERY group can
                # restore it (the windowed groups the window that ends there)
                found = self.allocator.match_prefix(hashes[:reusable])
                found = found[: self._hit_blocks(hashes, len(found))]
                prefix_ids = self.allocator.acquire(found)
            else:
                prefix_ids = self.allocator.acquire_prefix(hashes[:reusable])
            prefix_blocks = len(prefix_ids)
            blocks_needed = (
                (prompt_len + self.cfg.block_size - 1) // self.cfg.block_size
                - prefix_blocks
            )
            windows = 0
            if self._ring is not None:
                # a prompt's pages are a ring's at most (the family declines
                # prefix hits), and it opens a summary block a window: both
                # are reserved here or the request waits
                blocks_needed, windows = self._ring.held(prompt_len)
            if not self.allocator.can_allocate(blocks_needed) or not (
                self._take_summary_blocks(st, windows)
            ) or not self._admit_windows(st, hashes, prefix_blocks):
                self.allocator.release(prefix_ids)
                still.append(st)
                continue
            try:
                new_ids = self.allocator.allocate(blocks_needed)
            except OutOfBlocks:
                self.allocator.release(prefix_ids)
                self._release(st)
                still.append(st)
                continue
            st.block_ids = prefix_ids + new_ids
            st.cached_tokens = prefix_blocks * self.cfg.block_size
            # prompt blocks become content-addressed ONLY as their chunks'
            # KV is actually written (_commit_prefilled_blocks after each
            # chunk) — committing at admission would let a concurrent
            # request match pages that hold garbage, and a mid-prefill kill
            # would leak unwritten blocks into the reusable LRU
            st.commit_upto = prefix_blocks
            st.prefill_pos = st.cached_tokens
            st.slot = slot
            self._slots[slot] = st
            self._block_tables[slot].fill(0)
            self._block_tables[slot, : len(st.block_ids)] = st.block_ids
            self._table_summary_blocks(st)
            for g in range(len(self._win_groups)):
                self._table_window(st, g)
            self._seq_lens[slot] = prompt_len
            s = st.req.sampling
            self._temps[slot] = s.temperature
            self._top_ks[slot] = s.top_k
            self._top_ps[slot] = s.top_p
            self._min_ps[slot] = s.min_p
            self._pres[slot] = s.presence_penalty
            self._freqs[slot] = s.frequency_penalty
            self._reps[slot] = s.repetition_penalty
            self._lp_ns[slot] = min(max(s.logprobs, 0), TOP_LOGPROBS_K)
            big = np.iinfo(np.int32).max
            asked = st.req.stop.max_tokens
            self._max_new[slot] = big if asked is None else min(asked, big)
            seed = s.seed
            self._seeds[slot] = np.uint32(
                seed if seed is not None else self._host_rng.integers(1 << 32)
            )
            self._lora_slots[slot] = (
                self.lora.slot_of(st.req.annotations.get("lora"))
                if self.lora is not None else 0
            )
            self._lp_masks[slot, :] = False
            wanted = st.req.annotations.get("logits_processors") or []
            for k, (pname, _fn) in enumerate(self.cfg.logits_processors):
                if pname in wanted:
                    self._lp_masks[slot, k] = True
            if self.guided_enabled:
                if st.guided_tables is not None:
                    tt = st.guided_tables
                    S_g, C_g = tt.trans.shape
                    self._g_active[slot] = True
                    # guided_state was seeded at generate() (0, or walked
                    # over prior_token_ids for disagg/migration resumes)
                    self._g_state[slot] = st.guided_state
                    V_model = self._g_class.shape[1]
                    n = min(len(tt.class_of), V_model)
                    self._g_class[slot, :n] = tt.class_of[:n]
                    # model vocab beyond the tokenizer vocab has no byte
                    # form: map those ids to column C_g, which stays all -1
                    # (always-reject; the compile gate enforces C_g < cap)
                    self._g_class[slot, n:] = C_g
                    self._g_trans[slot].fill(-1)
                    self._g_trans[slot, :S_g, :C_g] = tt.trans
                    self._g_dirty_slots.add(slot)
                    self._g_active_version += 1
                elif self._g_active[slot]:
                    # previous occupant was guided: drop its mask before the
                    # new request's first dispatch. Non-guided -> non-guided
                    # turnover touches nothing (no upload on plain traffic).
                    self._g_active[slot] = False
                    self._g_active_version += 1
            # penalty tables: reset the slot's rows when this request uses
            # penalties (needs a fresh prompt mask) or a prior occupant left
            # them dirty. One tiny async dispatch; skipped entirely on the
            # common penalties-off path.
            has_pen = (
                s.presence_penalty != 0.0
                or s.frequency_penalty != 0.0
                or s.repetition_penalty != 1.0
            )
            st.counting = has_pen or bool(wanted)
            if st.counting or self._slot_dirty[slot]:
                row = np.zeros(self.mcfg.vocab_size, np.int8)
                if has_pen:
                    ids = np.asarray(st.seq.tokens(), np.int64)
                    # image placeholders sit above the vocab: they are not
                    # sampleable, so they simply don't enter the mask
                    row[ids[ids < self.mcfg.vocab_size]] = 1
                _, (self.prompt_masks, self.output_counts) = launch(
                    self, self._reset_slot_fn, 0,
                    self.prompt_masks, self.output_counts,
                    self._j(np.int32(slot)), self._j(row),
                )
            # counts accumulate for EVERY active slot while anyone counts
            # (update_counts scatters the full batch): a slot that shared a
            # batch with a counting request holds stale counts the next
            # occupant must not inherit
            batch_counting = st.counting or any(
                o is not None and o.counting for o in self._slots if o is not st
            )
            self._slot_dirty[slot] = batch_counting
            if st.counting:
                for j, other in enumerate(self._slots):
                    if other is not None and other is not st:
                        self._slot_dirty[j] = True
            admitted.append(st)
            st.t_admitted = now_ns()
            if self.stats_hook is not None:
                self._admit_waits.append(
                    (st.t_admitted - st.t_queued) / 1e9
                )
            get_flight_recorder().record(
                st.req.request_id, "admitted",
                slot=slot, cached_tokens=st.cached_tokens,
                prompt_tokens=prompt_len,
            )
            log.debug(
                "admit %s: %d tokens (%d cached), slot %d",
                st.req.request_id[:8], prompt_len, st.cached_tokens, slot,
            )
        self._waiting = still
        return admitted

    def _take_summary_blocks(self, st: _Seq, windows: int) -> bool:
        """A family with a ring: take what ``st`` lacks of a summary block a
        window for ``windows`` windows (one is taken as its window opens,
        all are released with the request). False, and nothing taken, where
        the store cannot give them; True at once for every other family."""
        more = windows - len(st.summary_ids)
        if self._ring is None or more <= 0:
            return True
        try:
            new_ids = self.summary_allocator.allocate(more)
        except OutOfBlocks:
            return False
        st.summary_ids.extend(new_ids)
        if st.slot >= 0 and self._slots[st.slot] is st:
            self._table_summary_blocks(st)
        return True

    def _table_summary_blocks(self, st: _Seq) -> None:
        """``st``'s summary blocks into its row of the table, behind the
        ring's entries, a window each."""
        if st.summary_ids:
            first = self._ring.pages
            self._block_tables[
                st.slot, first : first + len(st.summary_ids)
            ] = st.summary_ids

    # -- pages by layer kind: the windowed groups (allocator.WindowGroup) ----
    def _hit_blocks(self, hashes, n: int) -> int:
        """The longest prefix hit, in blocks and at most ``n``, that every
        windowed group can restore: a hit of ``P`` tokens needs each such
        group's pages covering ``[P - window, P)``. Where one is gone (its
        pool gave it up) the hit is shortened to end at it, and looked at
        again; 0 declines the hit. Never taken on a page that is not there."""
        bs = self.cfg.block_size
        while n > 0:
            for grp in self._win_groups:
                lo = grp.first_needed(n * bs)
                gone = next(
                    (i for i in range(n - 1, lo - 1, -1)
                     if grp.allocator.lookup(hashes[i]) is None), None,
                )
                if gone is not None:
                    n = gone
                    break
            else:
                break
        return n

    def _admit_windows(self, st: _Seq, hashes, prefix_blocks: int) -> bool:
        """Admission in every windowed group, or the request waits (False,
        nothing held): the pages of a hit's last window pinned, the pool able
        to give what the request will hold at most while it prefills, and its
        first chunk's pages taken."""
        if not self._win_groups:
            return True
        bs = self.cfg.block_size
        P = prefix_blocks * bs
        prompt_pages = -(-len(st.seq) // bs)
        st.win_first, st.win_ids = [], []
        for grp in self._win_groups:
            lo = grp.first_needed(P)
            st.win_first.append(lo)
            st.win_ids.append(grp.allocator.acquire([
                grp.allocator.lookup(h) for h in hashes[lo:prefix_blocks]
            ]))
            most = min(prompt_pages - lo, grp.pages) - (prefix_blocks - lo)
            if not grp.allocator.can_allocate(most):
                self._release_windows(st)
                return False
        if self._slide(st, P, min(len(st.seq), P + self.cfg.prefill_chunk)) is None:
            self._release_windows(st)
            return False
        return True

    def _slide(self, st: _Seq, q: int, upto: int):
        """Every windowed group of ``st`` moved on: the pages that lie wholly
        behind the window of a query at position ``q`` (the earliest any
        later step of ``st`` can ask) are let go (a reference dropped: a page
        a cached prefix or another request shares stays, and nothing is
        written in place), and pages are taken so that positions below
        ``upto`` have one. Returns the pages taken a group (for a caller
        that rolls back), or None, and nothing taken, where a pool cannot
        give them."""
        bs = self.cfg.block_size
        taken: List[int] = []
        for g, grp in enumerate(self._win_groups):
            ids = st.win_ids[g]
            drop = min(max(grp.first_needed(q) - st.win_first[g], 0), len(ids))
            if drop:
                grp.allocator.release(ids[:drop], behind=True)
                del ids[:drop]
                st.win_first[g] += drop
                grp.released += drop
            if not ids:
                st.win_first[g] = max(st.win_first[g], grp.first_needed(q))
            more = -(-upto // bs) - (st.win_first[g] + len(ids))
            if more > 0:
                try:
                    new_ids = grp.allocator.allocate(more)
                except OutOfBlocks:
                    self._unslide(st, taken)
                    return None
                st.win_ids[g] = ids + new_ids
                ids = st.win_ids[g]
            taken.append(max(more, 0))
            if len(ids) > grp.pages:
                raise RuntimeError(
                    f"a row holds {len(ids)} pages of a windowed group whose "
                    f"table has {grp.pages}"
                )
            # most calls move nothing (a token inside its page): the row's
            # run is written again only where it changed
            if (drop or more > 0) and st.slot >= 0 and self._slots[st.slot] is st:
                self._table_window(st, g)
        return taken

    def _slide_chunk(self, st: _Seq) -> bool:
        """Before ``st``'s next prefill chunk is launched: its windowed
        groups moved on to the chunk (True at once for a family of one
        group). False: a pool cannot give the pages now and the chunk waits
        for the other rows to let some go; where no other row is left to do
        so, the request ends as one that ran out of pages does."""
        if not self._win_groups:
            return True
        start = st.prefill_pos
        upto = min(len(st.seq), start + self.cfg.prefill_chunk)
        if self._slide(st, start, upto) is not None:
            return True
        if not any(s is not None and s is not st and not s.done
                   for s in self._slots):
            st.done = True
            st.out_queue.put_nowait(BackendOutput(
                finish_reason=FINISH_LENGTH, cumulative_tokens=st.produced,
            ))
        return False

    def _unslide(self, st: _Seq, taken: List[int]) -> None:
        """Give back the pages ``_slide`` took last (its return)."""
        for g, n in enumerate(taken):
            if n:
                ids = st.win_ids[g]
                self._win_groups[g].allocator.release(ids[-n:])
                del ids[-n:]
                if st.slot >= 0 and self._slots[st.slot] is st:
                    self._table_window(st, g)

    def _table_window(self, st: _Seq, g: int) -> None:
        """``st``'s run of windowed group ``g`` into its row of the table,
        and behind it the page index the run starts at."""
        grp, ids = self._win_groups[g], st.win_ids[g]
        row = self._block_tables[st.slot]
        row[grp.col : grp.col + grp.pages] = 0
        row[grp.col : grp.col + len(ids)] = ids
        row[grp.col + grp.pages] = st.win_first[g]

    def _commit_windows(self, st: _Seq, block: int, seq_hash) -> None:
        """A sealed block becomes content-addressed in every windowed group
        that still holds its page."""
        for g, grp in enumerate(self._win_groups):
            i = block - st.win_first[g]
            if 0 <= i < len(st.win_ids[g]):
                grp.allocator.commit(st.win_ids[g][i], seq_hash)

    def _release_windows(self, st: _Seq) -> None:
        for grp, ids in zip(self._win_groups, st.win_ids):
            grp.allocator.release(ids)
        st.win_first, st.win_ids = [], []

    def _release(self, st: _Seq) -> None:
        """Everything ``st`` holds back to the free lists: its pages (of
        every group) and, under a ring, the summary blocks of the windows it
        opened."""
        self.allocator.release(st.block_ids)
        if st.summary_ids:
            self.summary_allocator.release(st.summary_ids)
        self._release_windows(st)
        st.block_ids, st.summary_ids = [], []

    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prefill of {n} tokens exceeds largest bucket "
            f"{self.cfg.prefill_buckets[-1]}"
        )

    def _commit_prefilled_blocks(self, st: _Seq) -> None:
        """Event-loop thread, after a chunk lands: content-address the prompt
        blocks whose KV the chunk just wrote (and queue their host-tier
        offload). Only written blocks ever become matchable."""
        if st.no_cache:
            return
        hashes = st.seq.sequence_hashes()
        upto = min(st.prefill_pos // self.cfg.block_size, len(hashes))
        for i in range(st.commit_upto, upto):
            self.allocator.commit(st.block_ids[i], hashes[i])
            self._commit_windows(st, i, hashes[i])
            if self.kvbm is not None:
                self._offload_pending.append((st.block_ids[i], hashes[i], 0))
        if upto > st.commit_upto and self.kv_commits is not None:
            # wake streaming transfer fetches: this chunk's blocks are now
            # addressable, so a decode-side pull overlapping our remaining
            # prefill compute can ship them immediately
            self.kv_commits.fire()
        st.commit_upto = max(st.commit_upto, upto)

    # -- device calls (run in executor thread) -------------------------------
    def _chunk_arrays(self, token_ids, start: int, chunk_len: int, block_ids):
        """One prefill chunk's padded host arrays (shared by generation
        prefill and chunked embeddings — the padding conventions MUST match:
        pad positions pin to max_context-1, pad rows write scratch block 0).

        Returns (tokens [S_pad], positions [S_pad], new_block_ids
        [S_pad//bs])."""
        bs = self.cfg.block_size
        S_pad = self._bucket(chunk_len)
        tokens = np.zeros(S_pad, np.int32)
        tokens[:chunk_len] = token_ids[start : start + chunk_len]
        positions = np.full(S_pad, self.cfg.max_context - 1, np.int32)
        positions[:chunk_len] = np.arange(start, start + chunk_len)
        new_block_ids = np.zeros(S_pad // bs, np.int32)
        if self._ring is not None:
            # the chunk's own pages of the ring (it lies in one window: the
            # buckets divide it), none of the pages behind them, which hold
            # the window before
            first = self._ring.entry(start)
            real = block_ids[first : first + -(-chunk_len // bs)]
        else:
            real = block_ids[start // bs :][: S_pad // bs]
        new_block_ids[: len(real)] = real
        return tokens, positions, new_block_ids

    def _take_chunk_arrays(self, st: "_Seq", prompt, start: int,
                           chunk_len: int):
        """One chunk's packed arrays: the async step-prep pipeline's
        prebuild when it matches exactly (engine/prep.py — built and
        uploaded under the PREVIOUS step's device compute), else serial
        ``_chunk_arrays``. Returns ((tokens, positions, new_block_ids),
        device_uploads_or_None); outputs are byte-identical either way."""
        if self._prep is not None:
            got = self._prep.take(
                st.req.request_id, prompt, start, chunk_len, st.block_ids
            )
            if got is not None:
                return got
        return (
            self._chunk_arrays(prompt, start, chunk_len, st.block_ids),
            None,
        )

    def _schedule_next_chunk(self, st: "_Seq", prompt, is_final: bool) -> None:
        """Executor thread, right after a chunk's device call is dispatched
        (device compute is in flight from here): hand the NEXT chunk's
        packing + upload to the prep thread so step N+1's host prep runs
        under step N's device work."""
        if self._prep is None or is_final:
            return
        start = st.prefill_pos
        remaining = len(prompt) - start
        if remaining <= 0:
            return
        chunk_len = min(remaining, self.cfg.prefill_chunk)
        self._prep.schedule(
            st.req.request_id, prompt, start, chunk_len, st.block_ids
        )

    def _advance_draft_prefill(self, st: "_Seq", prompt) -> None:
        """Speculative decoding: bring the DRAFT cache's prompt coverage up
        to the main cache's. Driven off prefill_pos rather than the chunk
        just dispatched so regions the main cache acquired WITHOUT compute
        (prefix-cache hit, disagg/kvbm import set prefill_pos past 0) are
        draft-prefilled too — shared cached blocks get idempotent rewrites
        (same tokens => same draft KV). Draft coverage of the whole prompt
        is what keeps acceptance up; correctness never depends on it.
        Spec-ineligible requests skip it: their draft KV is never read
        (eligible batchmates cover shared prefix blocks themselves).
        Shared by the split prefill dispatch AND the fused mixed step."""
        if self.cfg.spec_draft is None or not st.spec_ok:
            return
        cap = self.cfg.prefill_chunk
        _j = self._j
        while st.draft_prefill_pos < st.prefill_pos:
            dstart = st.draft_prefill_pos
            dlen = min(st.prefill_pos - dstart, cap)
            dtok, dpos, dnb = self._chunk_arrays(
                prompt, dstart, dlen, st.block_ids
            )
            _, (self.draft_k_caches, self.draft_v_caches) = launch(
                self, self._draft_prefill_fn, len(dtok),
                self.draft_params, self.draft_k_caches,
                self.draft_v_caches, _j(dtok), _j(dpos),
                _j(self._block_tables[st.slot]), _j(dnb),
                _j(np.int32(dstart + dlen)),
            )
            st.draft_prefill_pos = dstart + dlen

    def _run_prefill_chunk(self, st: _Seq):
        """Prefill ONE bounded chunk of st's prompt (reference chunked
        prefill, protocols.rs:112): writes the chunk's KV pages; the final
        chunk also samples the first token. Returns None for intermediate
        chunks, else the (st, tok, lp, tlp..., the launch's seq) acceptance
        tuple."""
        with loop_span(self, "pack"):
            prompt = st.seq.tokens()
            start = st.prefill_pos
            remaining = len(prompt) - start
            cap = self.cfg.prefill_chunk
            is_final = remaining <= cap
            chunk_len = remaining if is_final else cap
            (tokens, positions, new_block_ids), dev = self._take_chunk_arrays(
                st, prompt, start, chunk_len
            )
            S_pad = len(tokens)  # the bucketed width (_mm_chunk needs it)

            total_len = start + chunk_len
            d_tokens, d_positions, d_new_blocks = (
                dev if dev is not None
                else (tokens, positions, new_block_ids)
            )
            g_dev = ()
            if self.guided_enabled:
                # full versioned device tables, indexed by slot in the
                # program; the FSM state travels by value (0, or walked over
                # prior tokens for disagg/migration resumes)
                g_dev = self._guided_dev()
            step = step_args.pack(
                self.cfg.max_batch_size, self._table_width,
                table_row=self._block_tables[st.slot],
                total_len=total_len, chunk_start=start, slot=st.slot,
                is_final=is_final, c_lp_need=self._lp_ns[st.slot] > 0,
                c_g_state=st.guided_state,
            )
        with loop_span(self, "upload"):
            args = self._upload((
                self.params, self.k_caches, self.v_caches, self.output_counts,
                d_tokens, d_positions, d_new_blocks, step,
                *self._slot_sampling_dev(),
                self.prompt_masks, self._lora_tables(),
                self._dev("lora_slots", self._lora_slots),
                self._dev("proc_masks", self._lp_masks),
                *self._mm_chunk(st, start, chunk_len, S_pad),
                *g_dev,
            ))
        with loop_span(self, "launch"):
            seq, (self.k_caches, self.v_caches, self.output_counts, tok, lp,
                  tlp_vals, tlp_ids) = launch(
                self, self._prefill_fn, S_pad, *args)
            self._count_state(0, chunk_len, 0)
        with loop_span(self, "pack"):
            # the next chunk's arrays, built under this chunk's compute
            del args  # donated caches: hold no stale handles
            st.prefill_pos = total_len
            self._schedule_next_chunk(st, prompt, is_final)
            self._advance_draft_prefill(st, prompt)
        if not is_final:
            return None
        # NO sync readback here: converting tok/lp on this thread would pay
        # a full device->host round trip per sequence — and wait for the
        # chunk's compute — serializing admission. The loop fetches on the
        # fetch pool, overlapping the waits across sequences.
        st.prefill_inflight = True
        tok.copy_to_host_async()
        lp.copy_to_host_async()
        want_tlp = self._lp_ns[st.slot] > 0
        return (st, tok, lp, tlp_ids if want_tlp else None,
                tlp_vals if want_tlp else None, seq)

    def _mm_chunk(self, st: _Seq, start: int, chunk_len: int, S_pad: int):
        """Per-chunk soft-token override arrays for the prefill program.
        Tiny dummies when the engine has no vision tower (statically
        ignored), zeros for text-only requests on a vision engine."""
        if self.cfg.vision is None:
            if self._mh is not None:  # host dummies: see _j
                return (np.zeros((1, 1), self.mcfg.dtype), np.zeros((1,), bool))
            # made once: a ``jnp.zeros`` a chunk is device programs of its
            # own beside the chunk's (four ``convert_element_type`` a lone
            # chunk, which no launch record names: PERF.md section 6, PR 51)
            if self._mm_none is None:
                self._mm_none = (
                    jnp.zeros((1, 1), self.mcfg.dtype), jnp.zeros((1,), bool)
                )
            return self._mm_none
        H = self.mcfg.hidden_size
        if st.mm_embeds is None:
            # text-only request on a vision engine: reuse one cached zero
            # pair per bucket instead of uploading S_pad x H zeros per chunk
            cached = self._mm_zero.get(S_pad)
            if cached is None:
                cached = (
                    jnp.zeros((S_pad, H), self.mcfg.dtype),
                    jnp.zeros((S_pad,), bool),
                )
                self._mm_zero[S_pad] = cached
            return cached
        embeds = np.zeros((S_pad, H), np.float32)
        mask = np.zeros((S_pad,), bool)
        span = slice(start, start + chunk_len)
        embeds[:chunk_len] = st.mm_embeds[span]
        mask[:chunk_len] = st.mm_mask[span]
        return (
            jnp.asarray(embeds, self.mcfg.dtype), jnp.asarray(mask)
        )

    def _encode_images(self, req: PreprocessedRequest) -> Tuple[np.ndarray, np.ndarray]:
        """Executor thread: decode+encode each image (through the encoder
        cache) and splice the patch embeddings over the prompt's placeholder
        runs. Returns (mm_embeds [L, H], mm_mask [L])."""
        from ..llm.encoder_cache import content_hash

        vcfg = self.cfg.vision
        H = self.mcfg.hidden_size
        tokens = np.asarray(req.token_ids, np.int64)
        L = len(tokens)
        embeds = np.zeros((L, H), np.float32)
        mask = tokens == self.cfg.image_token_id
        # contiguous placeholder runs, in order, one per image
        runs: List[Tuple[int, int]] = []
        i = 0
        while i < L:
            if mask[i]:
                j = i
                while j < L and mask[j]:
                    j += 1
                runs.append((i, j))
                i = j
            else:
                i += 1
        images = req.annotations.get("images") or []
        if len(runs) != len(images):
            raise ValueError(
                f"prompt has {len(runs)} image placeholder runs but request "
                f"carries {len(images)} images"
            )
        for (a, b), img in zip(runs, images):
            data = img["data"]
            key = content_hash(data)
            feats = self.encoder_cache.get(key)
            if feats is None:
                arr = np.frombuffer(data, np.float32).reshape(img["shape"])
                feats = np.asarray(
                    self._encode_image_fn(self.vision_params, jnp.asarray(arr)),
                    np.float32,
                )
                self.encoder_cache.set(key, feats)
            if b - a != feats.shape[0]:
                raise ValueError(
                    f"image placeholder run of {b - a} tokens != "
                    f"{feats.shape[0]} patch embeddings"
                )
            embeds[a:b] = feats
        return embeds, mask

    def _run_embed(self, token_ids: List[int],
                   block_ids: Optional[List[int]] = None) -> np.ndarray:
        S = len(token_ids)
        if block_ids is None:
            # fits one dispatch: dense causal forward, no pages touched
            S_pad = self._bucket(S)
            tokens = np.zeros(S_pad, np.int32)
            tokens[:S] = token_ids
            positions = np.arange(S_pad, dtype=np.int32)
            seq, vec = launch(
                self, self._embed_fn, S_pad,
                self.params, self._j(tokens), self._j(positions),
                self._j(np.int32(S - 1)),
            )
            return self._read_embedding(seq, vec)
        # chunked: the caller pre-allocated temporary pages (loop thread
        # owns the allocator); each chunk writes KV + attends over the
        # gathered prefix, the final chunk yields the pooled vector
        if self.state is not None or self._ring is not None or self._win_groups:
            raise ValueError(
                "an embedding input above the largest prefill bucket is not "
                "served by a family with slot state, a ring or pages by "
                "layer kind: temporary pages of ONE table carry keys from "
                "chunk to chunk, nothing carries a recurrent state, a "
                "window's summaries or a second group's table"
            )
        cap = self.cfg.prefill_chunk
        table = np.zeros(self._table_width, np.int32)
        table[: len(block_ids)] = block_ids
        vec = None
        _j = self._j
        for start in range(0, S, cap):
            chunk_len = min(cap, S - start)
            is_final = start + chunk_len >= S
            tokens, positions, nbi = self._chunk_arrays(
                token_ids, start, chunk_len, block_ids
            )
            seq, (self.k_caches, self.v_caches, vec) = launch(
                self, self._embed_chunk_fn, len(tokens),
                self.params, self.k_caches, self.v_caches,
                _j(tokens), _j(positions), _j(table), _j(nbi),
                _j(np.int32(start + chunk_len)),
                _j(np.int32(chunk_len - 1)),
                _j(np.bool_(is_final)),
            )
        return self._read_embedding(seq, vec)

    def _run_mixed_step(self, st: _Seq, seqs: List[Optional["_Seq"]],
                        prev: Optional[_Chain], at_once: bool):
        """Executor thread: ONE fused dispatch serving st's next prefill
        chunk AND a single decode step for the ``seqs`` snapshot (the mixed
        continuous-batching step; engine _build_programs mixed_step), as a
        link of the decode chain. With ``prev`` given (the mixed link
        launched before this one, not read yet) the rows it advanced take
        their token from its device carry. The results' readback starts at
        once; they are read here, under ``sync``, only where the loop needs
        them before it can build the next step (``at_once``), else by the
        loop's ``fetch`` a tick later, behind the next launch.
        Returns (the link, prefill result tuple like _run_prefill_chunk's
        or None for intermediate chunks)."""
        with loop_span(self, "pack"):
            prompt = st.seq.tokens()
            start = st.prefill_pos
            remaining = len(prompt) - start
            cap = self.cfg.prefill_chunk
            is_final = remaining <= cap
            chunk_len = remaining if is_final else cap
            (tokens, positions, new_block_ids), dev = self._take_chunk_arrays(
                st, prompt, start, chunk_len
            )
            prep_hit = self._prep.pop_last() if self._prep is not None else None
            (d_positions, d_seq_lens, write_blocks, write_offsets, steps,
             carried) = self._decode_dispatch_arrays(seqs, prev)
            lp_need = bool(np.any((self._lp_ns > 0) & (d_seq_lens > 0)))
            c_lp_need = self._lp_ns[st.slot] > 0
            g_dev, g_rows = (), {}
            if self.guided_enabled:
                # decode rows resync the host FSM states (a mixed step
                # with a guided decode row is read at once, so the next
                # step finds them walked); the chunk row's state travels
                # by value like prefill
                g_dev = self._guided_dev()
                g_rows = dict(g_state=self._g_state)
            d_tokens, d_pos_chunk, d_new_blocks = (
                dev if dev is not None
                else (tokens, positions, new_block_ids)
            )
            step = step_args.pack(
                self.cfg.max_batch_size, self._table_width,
                table_row=self._block_tables[st.slot],
                total_len=start + chunk_len, chunk_start=start, slot=st.slot,
                is_final=is_final, c_lp_need=c_lp_need, lp_need=lp_need,
                c_g_state=st.guided_state,
                tokens=self._tokens, positions=d_positions,
                seq_lens=d_seq_lens, write_blocks=write_blocks,
                write_offsets=write_offsets, steps=steps, carried=carried,
                **g_rows,
            )
        with loop_span(self, "upload"):
            args = self._upload((
                self.params, self.k_caches, self.v_caches, self.output_counts,
                d_tokens, d_pos_chunk, d_new_blocks, step,
                # on the device either way, and no placement: the link's
                # sampled tokens, or a constant placed once as a program's
                # result is (so both come to the one compiled program)
                prev.tokens if prev is not None else self._no_carry,
                self._dev("tables", self._block_tables),
                *self._slot_sampling_dev(),
                self.prompt_masks, self._lora_tables(),
                self._dev("lora_slots", self._lora_slots),
                self._dev("proc_masks", self._lp_masks),
                *g_dev,
            ))
        with loop_span(self, "launch"):
            t0_ns = now_ns()
            seq, (
                self.k_caches, self.v_caches, self.output_counts, toks, lps,
                tlp_vals, tlp_ids, c_tok, c_lp, c_tlp_vals, c_tlp_ids,
                seq_lens, next_steps,
            ) = launch(self, self._mixed_fn, len(tokens), *args)
            # the readback starts now: by the link's turn to be read the
            # bytes are on the host (as a horizon's packed results)
            results = (toks, lps) + ((tlp_ids, tlp_vals) if lp_need else ())
            for x in results:
                x.copy_to_host_async()
            # the rows' share is counted when the link is read: what was kept
            self._count_state(0, chunk_len, 0)
            link = _Chain(
                results, toks, seq_lens, next_steps, seqs, length=1,
                seq=seq, mixed=True, chunk_tokens=chunk_len,
                chained=prev is not None, t0_ns=t0_ns, prep_hit=prep_hit,
                placed=self._h2d_placements,
            )
            self._h2d_placements = 0
        with loop_span(self, "pack"):
            # the next chunk's arrays, built under this step's compute
            del args  # donated caches: hold no stale handles
            st.prefill_pos = start + chunk_len
            self._schedule_next_chunk(st, prompt, is_final)
            self._advance_draft_prefill(st, prompt)
        if at_once:
            with loop_span(self, "sync"):
                link.results = self._decode_results(seq, seqs, *results)
                self._took(seq)
        else:
            link.fetch = self._fetch_executor.submit(
                self._decode_results, seq, seqs, *results
            )
        prefill_res = None
        if is_final:
            # same async-readback protocol as _run_prefill_chunk: the loop
            # hands these to the fetch pool so the D2H RTT overlaps. No seq:
            # the launch's one arrival is its decode rows' (_decode_results)
            st.prefill_inflight = True
            c_tok.copy_to_host_async()
            c_lp.copy_to_host_async()
            prefill_res = (st, c_tok, c_lp,
                           c_tlp_ids if c_lp_need else None,
                           c_tlp_vals if c_lp_need else None, -1)
        return link, prefill_res

    def _book_decode_blocks(
        self, seqs: List[Optional["_Seq"]], extra_tokens: int,
        prev: Optional[_Chain] = None,
    ) -> bool:
        """Pre-allocate pages so every active (prefilled, unfinished)
        sequence in ``seqs`` can absorb ``extra_tokens`` more decode tokens,
        beyond the ``prev.length`` that a row of the unread link ``prev``
        has in flight (the host's length does not hold them yet).
        All-or-nothing: on any failure (context headroom, block pressure)
        every block this call took is given back — otherwise the fallback
        path itself starves (the blocks would sit idle until finish). The
        one booking routine behind both the horizon dispatch
        (_prepare_horizon) and the fused mixed step (_prepare_mixed), so
        the split and fused paths can never drift."""
        bs = self.cfg.block_size
        ring = self._ring
        # (sequence, pages, summary blocks): rollback on partial failure
        granted: List[Tuple[_Seq, int, int]] = []
        slid: List[Tuple[_Seq, List[int]]] = []
        ok = True
        for i, st in enumerate(seqs):
            if st is None or st.done or not st.prefilled:
                continue
            L = len(st.seq) + self._in_flight(prev, i, st)
            if L + extra_tokens >= self.cfg.max_context:
                ok = False
                break
            needed = (L + extra_tokens) // bs + 1
            if ring is not None:
                # a whole ring at most; and a summary block a window the
                # tokens booked here may open
                needed, windows = ring.held(L + extra_tokens + 1)
                held = len(st.summary_ids)
                if not self._take_summary_blocks(st, windows):
                    ok = False
                    break
                if len(st.summary_ids) > held:
                    granted.append((st, 0, len(st.summary_ids) - held))
            if self._win_groups:
                # every windowed group: behind the host's last token let
                # go, a page for every position booked here
                took = self._slide(st, len(st.seq) - 1, L + extra_tokens + 1)
                if took is None:
                    ok = False
                    break
                slid.append((st, took))
            extra = needed - len(st.block_ids)
            if extra > 0:
                if not self.allocator.can_allocate(extra):
                    ok = False
                    break
                try:
                    new_ids = self.allocator.allocate(extra)
                except OutOfBlocks:
                    ok = False
                    break
                base = len(st.block_ids)
                st.block_ids.extend(new_ids)
                for off, bid in enumerate(new_ids):
                    self._block_tables[st.slot, base + off] = bid
                granted.append((st, len(new_ids), 0))
        if not ok:
            for st, took in slid:
                self._unslide(st, took)
            for st, count, blocks in granted:
                if count:
                    taken = st.block_ids[-count:]
                    del st.block_ids[-count:]
                    self.allocator.release(taken)
                if blocks:
                    taken = st.summary_ids[-blocks:]
                    del st.summary_ids[-blocks:]
                    self.summary_allocator.release(taken)
            return False
        return True

    def _prepare_mixed(self, seqs: List[Optional["_Seq"]],
                       prev: Optional[_Chain] = None) -> bool:
        """Book a mixed step: the chunk's pages were booked at admission
        (_try_admit allocates the whole prompt), so this books the DECODE
        half — every active row gets headroom for the one token the fused
        step advances, past the one the unread link ``prev`` has in flight.
        False => fall back to the split prefill dispatch (or, with
        ``prev``, read it first)."""
        return self._book_decode_blocks(seqs, 1, prev)

    def _prepare_horizon(self) -> bool:
        """Pre-allocate pages so every active sequence can absorb one more
        decode horizon on top of what the in-flight chain advances (a
        horizon its decode_steps, a mixed link one token). False => fall
        back to the single-step program (block pressure or a sequence
        within a horizon of max_context)."""
        n = self.cfg.decode_steps
        if n <= 1:
            return False
        ahead = sum(c.length for c in self._chains)
        return self._book_decode_blocks(self._slots, ahead + n)

    def _lora_tables(self):
        return self.lora.tables() if self.lora is not None else {}

    def _fetch_prefill_result(self, st, tok, lp, tlp_ids, tlp_vals, seq):
        """Fetch pool thread: the blocking device->host conversion, and the
        arrival of launch ``seq``'s results (a lone chunk's; -1: none)."""
        res = (
            st, int(tok), float(lp),
            np.asarray(tlp_ids) if tlp_ids is not None else None,
            np.asarray(tlp_vals) if tlp_vals is not None else None,
        )
        record_arrival(self, seq)
        return res

    def _fetch_packed(self, chain: _Chain) -> np.ndarray:
        """Fetch pool thread: a horizon's packed results, and their arrival."""
        packed = np.asarray(chain.packed)
        record_arrival(self, chain.seq)
        return packed

    def _took(self, seq: int) -> None:
        """The loop has the results of launch ``seq`` in hand: what the
        record of a later launch says it came ``after``."""
        if seq > self._read_seq:
            self._read_seq = seq

    def _read_embedding(self, seq: int, vec) -> np.ndarray:
        """Executor thread: the pooled vector of launch ``seq``, read at
        once: its arrival, and taken."""
        out = np.asarray(vec)
        record_arrival(self, seq)
        self._took(seq)
        return out

    async def _finish_prefill(self, st: "_Seq", fut, seq: int) -> None:
        """Loop thread: apply a prefill's first token once its readback
        (launch ``seq``'s, -1 where it rides a mixed link's) lands; the
        sequence becomes decode-eligible here."""
        try:
            _st, tok, lp, tlp_ids, tlp_vals = await asyncio.wrap_future(fut)
        except Exception:
            # readback died: fail the request instead of wedging the slot
            # (prefill_inflight stuck True would exclude it from every list
            # forever and busy-spin the loop)
            log.exception("prefill readback failed")
            st.prefill_inflight = False
            st.done = True
            evac = self._evacuation_plan(st)
            st.out_queue.put_nowait(BackendOutput(
                finish_reason="error", cumulative_tokens=st.produced,
                annotations={"evacuation": evac} if evac else {},
            ))
            self._wake.set()
            return
        st.prefill_inflight = False
        self._took(seq)
        if st.done or self._slots[st.slot] is not st:
            return  # cancelled/reaped while the fetch was in flight
        st.prefilled = True
        self._accept_token(st, tok, lp, tlp_ids, tlp_vals)
        self._wake.set()

    # How a dispatch's arguments travel (PERF.md section 6, PR 29: on a TPU
    # v5e each host value handed over is a transfer of its own, 0.15-0.25 ms):
    # what is on the device already (weights, caches, the prep thread's chunk
    # arrays) passes through; per-slot state, which changes on admission,
    # finish or a new page, is a cached device copy (``_dev``); what changes
    # every step is ONE fresh int32 buffer (step_args.py) that the jitted
    # call places itself (``_upload``). Multihost hands host numpy over in
    # every case: the leader wrapper broadcasts host data.

    def _j(self, host_val):
        """Place ONE host value now, for the calls no step loop makes
        (slot reset, draft prefill, embeddings). Multihost passes host
        numpy through: pulling an uploaded array straight back for the
        broadcast would pay a blocking D2H per argument."""
        return host_val if self._mh is not None else jnp.asarray(host_val)

    def _upload(self, args: tuple) -> tuple:
        """A step dispatch's arguments as the jitted call takes them. The
        host values left in ``args`` (the step's packed buffer, a chunk's
        arrays the prep thread did not place) stay numpy: the call places
        each in one transfer, and they must be fresh arrays, since the loop
        writes its slot arrays after the dispatch. Counted here and in
        ``_dev`` for ``StepStats.h2d_placements``."""
        self._h2d_placements += sum(
            isinstance(a, (np.ndarray, np.generic)) for a in args
        )
        return args

    def _dev(self, name: str, host_arr: np.ndarray) -> jax.Array:
        """Device-resident copy of per-slot state, placed again only when
        its content changed (a compare, not a transfer, on a steady step).
        One cache under one set of names for every program, so a mixed step
        after a horizon finds the arrays placed, and the other way round;
        only the caches and the counts are donated, so a copy is handed to
        program after program."""
        if self._mh is not None:
            # multihost dispatches travel as host numpy anyway (the leader
            # wrapper would immediately pull a device copy back); snapshot so
            # later slot mutations can't race the in-flight frame
            return host_arr.copy()
        cached = self._dev_cache.get(name)
        if cached is None or not np.array_equal(cached[1], host_arr):
            # place the snapshot, not the live array: the CPU backend may
            # alias a numpy array's memory
            snap = host_arr.copy()
            cached = self._dev_cache[name] = (jnp.asarray(snap), snap)
            self._h2d_placements += 1
        return cached[0]

    def _slot_sampling_dev(self) -> tuple:
        """The per-slot sampling state in the order every step program
        takes it: seeds, temps, top_ks, top_ps, min_ps, pres, freqs, reps."""
        dev = self._dev
        return (
            dev("seeds", self._seeds), dev("temps", self._temps),
            dev("top_ks", self._top_ks), dev("top_ps", self._top_ps),
            dev("min_ps", self._min_ps), dev("pres", self._pres),
            dev("freqs", self._freqs), dev("reps", self._reps),
        )

    async def _compile_guided(self, spec: Dict[str, Any]):
        """Grammar spec -> TokenTables, compiled off the event loop and
        cached by content (concurrent requests overwhelmingly share one
        schema). Raises ValueError for malformed grammars or ones whose
        automaton exceeds the engine's device-table caps."""
        import json as _json

        from ..guided import (
            RegexError, SchemaError, build_token_tables, compile_regex,
            guided_regex_pattern,
        )

        kind = spec.get("kind")
        key = _json.dumps(spec, sort_keys=True, default=str)

        def compile_():
            pattern = guided_regex_pattern(kind, spec.get("value"))
            # construction bound: subset construction can overshoot before
            # minimization shrinks it (generic JSON: ~5x), so allow headroom
            # over the engine cap — but check the MINIMIZED count before the
            # O(S x V) token product materializes anything vocab-sized
            dfa = compile_regex(
                pattern,
                max_states=min(32768, 32 * self.cfg.guided_max_states),
            )
            if dfa.num_states > self.cfg.guided_max_states:
                raise ValueError(
                    f"guided grammar needs {dfa.num_states} states > engine "
                    f"cap {self.cfg.guided_max_states}"
                )
            return build_token_tables(dfa, self._g_vocab, self._g_eos)

        def checked_compile():
            tt = compile_()
            if tt.num_classes >= self.cfg.guided_max_classes:
                # strict: column C_g of the padded table is the always-
                # reject class for model-vocab ids beyond the tokenizer
                # vocab
                raise ValueError(
                    f"guided grammar needs {tt.num_classes} token classes "
                    f">= engine cap {self.cfg.guided_max_classes}"
                )
            return tt

        # cache the in-flight FUTURE, not just the result: a burst of
        # requests sharing one schema (the common case) must not each run
        # the O(S x V) token-table product concurrently
        loop = asyncio.get_event_loop()
        task = self._g_cache.get(key)
        if task is None:
            task = asyncio.ensure_future(
                loop.run_in_executor(self._fetch_executor, checked_compile)
            )
            if len(self._g_cache) > 32:
                self._g_cache.pop(next(iter(self._g_cache)))
            self._g_cache[key] = task
        try:
            return await asyncio.shield(task)
        except (RegexError, SchemaError, ValueError) as e:
            # failures don't poison the cache (a later identical request
            # re-validates — caps may be config-reloaded across restarts)
            if self._g_cache.get(key) is task:
                del self._g_cache[key]
            raise GuidedRejectedError(f"guided grammar rejected: {e}") from e

    def _guided_dev(self):
        """Device copies of the guided tables. The [B] active mask
        re-uploads on its own version (admissions AND releases move it);
        the big tables upload once, then changed SLOTS scatter in as row
        updates (.at[slot].set — only the row crosses host->device, the
        rest is an on-device copy). [B, S, C] is far too big for _dev's
        per-dispatch content compare or per-admission full re-upload.

        Multihost: the tables are replay STATE — the leader pushes the same
        incremental updates through the guided_active/guided_row ops, so
        followers' handles stay in step and the decode dispatches reference
        them as state_in instead of broadcasting megabytes per horizon."""
        if self._mh is not None:
            if self._dev_cache.get("g/aver") != self._g_active_version:
                self._g_dev_active = self._mh_guided_active(
                    self._g_active.copy()
                )
                self._dev_cache["g/aver"] = self._g_active_version
            if self._g_dirty_slots:
                for slot in sorted(self._g_dirty_slots):
                    self._g_dev_class, self._g_dev_trans = (
                        self._mh_guided_row(
                            self._g_dev_class, self._g_dev_trans,
                            self._g_class[slot].copy(),
                            self._g_trans[slot].copy(),
                            np.int32(slot),
                        )
                    )
                self._g_dirty_slots.clear()
            return self._g_dev_active, self._g_dev_class, self._g_dev_trans
        if self._dev_cache.get("g/aver") != self._g_active_version:
            self._dev_cache["g/active"] = jnp.asarray(self._g_active)
            self._dev_cache["g/aver"] = self._g_active_version
        if self._dev_cache.get("g/class") is None:
            self._dev_cache["g/class"] = jnp.asarray(self._g_class)
            self._dev_cache["g/trans"] = jnp.asarray(self._g_trans)
            self._g_dirty_slots.clear()
        elif self._g_dirty_slots:
            gc, gt = self._dev_cache["g/class"], self._dev_cache["g/trans"]
            for slot in sorted(self._g_dirty_slots):
                gc = gc.at[slot].set(jnp.asarray(self._g_class[slot]))
                gt = gt.at[slot].set(jnp.asarray(self._g_trans[slot]))
            self._dev_cache["g/class"], self._dev_cache["g/trans"] = gc, gt
            self._g_dirty_slots.clear()
        return (
            self._dev_cache["g/active"],
            self._dev_cache["g/class"],
            self._dev_cache["g/trans"],
        )

    def _decode_snapshot(
        self, prev: Optional[_Chain] = None
    ) -> List[Optional["_Seq"]]:
        """Loop-thread snapshot of decode-eligible slots. MUST be taken on
        the loop thread in the same tick as _can_chain/_prepare_horizon: an
        async prefill finishing mid-dispatch would otherwise widen the
        active mask after those checks (stale carry token -> wrong KV).
        With ``prev``, an unread mixed link, a row of it that the host can
        foresee ending on the token in flight (``max_tokens`` reached, the
        caller gone) is left out: nothing is computed past such a finish.
        One it cannot foresee (a stop token) costs one discarded token."""
        snap = [
            st if (st is not None and not st.done and st.prefilled) else None
            for st in self._slots
        ]
        if prev is not None:
            for i, st in enumerate(snap):
                ahead = self._in_flight(prev, i, st)
                if not ahead:
                    continue
                limit = st.req.stop.max_tokens
                if (
                    (limit is not None and st.produced + ahead >= limit)
                    or st.context.is_stopped()
                ):
                    snap[i] = None
        return snap

    @staticmethod
    def _in_flight(prev: Optional[_Chain], i: int, st: Optional["_Seq"]) -> int:
        """Tokens of the row ``st`` in slot ``i`` that the unread link
        ``prev`` has sampled and the host has not read (0: not a row of
        it): what the host's length, step count and booking lack."""
        if prev is None or st is None or prev.seqs[i] is not st:
            return 0
        return prev.length

    def _dispatch_horizon(
        self, chain: Optional[_Chain], seqs: List[Optional["_Seq"]]
    ) -> _Chain:
        """Enqueue one multi-step decode over the loop-thread ``seqs``
        snapshot. With ``chain`` given, the carry (tokens/seq_lens/steps)
        comes straight from the in-flight dispatch — no host round-trip;
        otherwise it is synced up from host state."""
        with loop_span(self, "pack"):
            B = self.cfg.max_batch_size
            active = np.zeros(B, bool)
            for i, st in enumerate(seqs):
                if st is not None:
                    active[i] = True
            if chain is not None:
                tokens, seq_lens, steps = (
                    chain.tokens, chain.seq_lens, chain.steps
                )
            else:
                seq_lens_np = np.zeros(B, np.int32)
                steps_np = np.zeros(B, np.int32)
                for i, st in enumerate(seqs):
                    if st is None:
                        continue
                    seq_lens_np[i] = len(st.seq)
                    steps_np[i] = st.produced
                    self._tokens[i] = st.last_token
                # a resynced carry is per-step host data: three fresh numpy
                # arrays the jitted call places itself (see _upload; _tokens
                # is snapshotted, the loop mutates it after dispatch). In
                # multihost mode numpy-vs-jax.Array is also the carry/resync
                # signal (engine _wire_multihost carry_in).
                tokens = self._tokens.copy()
                seq_lens = seq_lens_np
                steps = steps_np
            spec = self.cfg.spec_draft is not None and self._spec_eligible(seqs)
            if spec:
                args = (
                    self.params, self.draft_params, self.k_caches,
                    self.v_caches, self.draft_k_caches, self.draft_v_caches,
                    tokens, seq_lens,
                    self._dev("tables", self._block_tables),
                    self._dev("active", active),
                    steps,
                    self._lora_tables(),
                    self._dev("lora_slots", self._lora_slots),
                )
            else:
                g_args = ()
                if self.guided_enabled:
                    g_active, g_class, g_trans = self._guided_dev()
                    g_state = (
                        chain.g_state
                        if chain is not None and chain.g_state is not None
                        else self._g_state.copy()
                    )
                    g_args = (g_active, g_state, g_class, g_trans)
                seeds_dev, *sampling_dev = self._slot_sampling_dev()
                args = (
                    self.params, self.k_caches, self.v_caches,
                    self.output_counts,
                    tokens, seq_lens,
                    self._dev("tables", self._block_tables),
                    self._dev("active", active),
                    seeds_dev, steps, *sampling_dev,
                    self.prompt_masks,
                    self._dev("lp_need", np.any(self._lp_ns[active] > 0)),
                    self._lora_tables(),
                    self._dev("lora_slots", self._lora_slots),
                    self._dev("proc_masks", self._lp_masks),
                    *g_args,
                )
            args = self._upload(args)
            # slot state: a row's recurrence stops at what its request asked
            quota = (
                {} if self.state is None
                else {"max_new": self._dev("max_new", self._max_new)}
            )
        # no "sync" here: a horizon's results are awaited by the loop ("fetch")
        with loop_span(self, "launch"):
            if spec:
                seq, (
                    self.k_caches, self.v_caches, self.draft_k_caches,
                    self.draft_v_caches, packed, tokens, seq_lens, steps,
                ) = launch(self, self._spec_multi_fn, self.cfg.spec_k, *args)
                packed.copy_to_host_async()
                return _Chain(
                    packed, tokens, seq_lens, steps, seqs, seq=seq,
                    spec_k=self.cfg.spec_k, length=self.cfg.decode_steps,
                )
            seq, res = launch(
                self, self._decode_multi_fn, self.cfg.decode_steps,
                *args, **quota)
            del args  # donated caches: hold no stale handles
            g_state_out = None
            if self.guided_enabled:
                (self.k_caches, self.v_caches, self.output_counts, packed,
                 tokens, seq_lens, steps, g_state_out) = res
            else:
                (self.k_caches, self.v_caches, self.output_counts, packed,
                 tokens, seq_lens, steps) = res
            # start the D2H readback immediately: by the time this horizon's
            # turn to be applied comes (decode_pipeline-1 horizons later) the
            # bytes are already on host and np.asarray is a no-wait copy
            packed.copy_to_host_async()
            return _Chain(
                packed, tokens, seq_lens, steps, seqs, g_state=g_state_out,
                length=self.cfg.decode_steps, seq=seq,
            )

    def _spec_eligible(self, seqs: List[Optional["_Seq"]]) -> bool:
        """Every active row must be greedy with no sampling-state coupling:
        temperature 0 (verify argmax == sample_tokens at temp 0), no
        penalties / logits processors (spec skips the counts machinery), no
        top-logprobs (the packed spec format carries token logprobs only).
        Mixed batches fall back to the normal horizon for the whole dispatch
        — eligibility is per-request-static, so the set only changes on
        admission/finish, which already breaks chains via _can_chain."""
        for i, st in enumerate(seqs):
            if st is None:
                continue
            if (
                self._temps[i] != 0.0
                or self._lp_ns[i] != 0
                or self._pres[i] != 0.0
                or self._freqs[i] != 0.0
                or self._reps[i] != 1.0
                or bool(self._lp_masks[i].any())
                # guided rows need the per-step FSM mask, which the spec
                # draft/verify programs do not carry
                or (self.guided_enabled and bool(self._g_active[i]))
            ):
                return False
        return True

    def _can_chain(self, chain: _Chain) -> bool:
        """A new horizon may ride on ``chain``'s device carry only if every
        currently-active slot holds the same sequence it held at dispatch —
        an admission into a recycled slot would decode from a stale carry."""
        for i, st in enumerate(self._slots):
            if (
                st is not None and not st.done and st.prefilled
                and chain.seqs[i] is not st
            ):
                return False
        return True

    def _apply_packed(self, chain: _Chain, packed_np: np.ndarray) -> None:
        """Apply one consumed horizon [N, B, 2+2K]: feed each snapshot slot's
        tokens through stop handling in order; the speculated tail past a
        finish is discarded. Each sequence's surviving tokens leave as ONE
        BackendOutput — per-token queue round-trips made horizon emission
        the dominant serving cost at batch>=16 (~1ms/token of asyncio churn
        against a ~0.9ms/token device program)."""
        if chain.spec_k is not None:
            return self._apply_packed_spec(chain, packed_np)
        # a consumed horizon: its snapshot's rows, ``decode_steps`` steps
        # each from the context the host holds now (earlier links are read)
        self._count_paged(
            chain.seqs, [s and len(s.seq) for s in chain.seqs],
            self.cfg.decode_steps,
        )
        if self.state is not None:
            # a consumed horizon advanced each of its snapshot's rows until
            # the row had sampled what its request asked (decode_multi)
            n = self.cfg.decode_steps
            self._count_state(
                sum(
                    n if st.req.stop.max_tokens is None
                    else min(n, max(st.req.stop.max_tokens - st.produced, 0))
                    for st in chain.seqs if st is not None
                ), 0, n,
            )
        K = TOP_LOGPROBS_K
        toks = packed_np[:, :, 0].astype(np.int32)
        lps = packed_np[:, :, 1]
        tlp_ids = packed_np[:, :, 2 : 2 + K].astype(np.int32)
        tlp_vals = packed_np[:, :, 2 + K : 2 + 2 * K]
        if self._moe_counted:
            # three more columns, the same in every row: a horizon sums its
            # steps' routed rows and touched experts, and keeps the largest load
            moe = packed_np[:, 0, 2 + 2 * K :]
            self._moe_last = (
                int(moe[:, 0].sum()), int(moe[:, 1].sum()), int(moe[:, 2].max()),
                *(int(x) for x in moe[:, 3:].sum(axis=0)),
            )
        for i, st in enumerate(chain.seqs):
            if st is None or st.done:
                continue
            want_tlp = st.req.sampling.logprobs > 0
            self._accept_tokens(
                st, [int(t) for t in toks[:, i]], [float(x) for x in lps[:, i]],
                tlp_ids[:, i] if want_tlp else None,
                tlp_vals[:, i] if want_tlp else None,
            )

    def _apply_packed_spec(self, chain: _Chain, packed_np: np.ndarray) -> None:
        """Apply one speculative horizon [R, B, 1+2k]: each round contributed
        a variable 1..k tokens per row (the advance count in column 0); the
        rest flows through the same _accept_tokens stop handling as a normal
        horizon."""
        sk = chain.spec_k
        R = packed_np.shape[0]
        for i, st in enumerate(chain.seqs):
            if st is None or st.done:
                continue
            toks: List[int] = []
            lps: List[float] = []
            for r in range(R):
                adv = int(packed_np[r, i, 0])
                row = packed_np[r, i]
                toks.extend(int(t) for t in row[1 : 1 + adv])
                lps.extend(float(x) for x in row[1 + sk : 1 + sk + adv])
            self.spec_stats["rounds"] += R
            self.spec_stats["emitted"] += len(toks)
            self._accept_tokens(st, toks, lps, None, None)

    def _decode_dispatch_arrays(self, seqs: List[Optional["_Seq"]],
                                prev: Optional[_Chain] = None):
        """Per-slot host arrays for ONE decode step over the ``seqs``
        snapshot — shared by _run_decode and _run_mixed_step so the
        write-block math and carry conventions can never drift between the
        split and fused paths. Also refreshes self._tokens with each row's
        fed token. A row that the unread mixed link ``prev`` advanced is
        ``carried``: its token is on the device, and everything else is the
        host's length so far plus the one token in flight. Returns
        (positions, seq_lens, write_blocks, write_offsets, steps, carried),
        all [B]."""
        bs, ring = self.cfg.block_size, self._ring
        B = self.cfg.max_batch_size
        positions = np.zeros(B, np.int32)
        seq_lens = np.zeros(B, np.int32)
        write_blocks = np.zeros(B, np.int32)
        write_offsets = np.zeros(B, np.int32)
        steps = np.zeros(B, np.int32)
        carried = np.zeros(B, np.int32)
        for i, st in enumerate(seqs):
            if st is None:
                continue
            ahead = self._in_flight(prev, i, st)
            L = len(st.seq) + ahead            # includes the token being fed
            positions[i] = L - 1
            seq_lens[i] = L
            carried[i] = ahead
            self._tokens[i] = 0 if ahead else st.last_token
            write_blocks[i] = st.block_ids[
                (L - 1) // bs if ring is None else ring.entry(L - 1)
            ]
            write_offsets[i] = (L - 1) % bs
            steps[i] = st.produced + ahead
        return positions, seq_lens, write_blocks, write_offsets, steps, carried

    def _decode_results(self, seq: int, seqs: List[Optional["_Seq"]], toks,
                        lps, tlp_ids=None, tlp_vals=None):
        """Device outputs of one decode step (launch ``seq``) ->
        (per-sequence acceptance tuples, the step's routing counters or
        None); shared by _run_decode and _run_mixed_step, the top-logprob
        rows only where a row asked. Touches no engine state but the
        arrival it stamps: a mixed link's runs on the fetch pool."""
        toks_np = np.asarray(toks)
        lps_np = np.asarray(lps)
        tlp_ids_np = np.asarray(tlp_ids) if tlp_ids is not None else None
        tlp_vals_np = np.asarray(tlp_vals) if tlp_vals is not None else None
        record_arrival(self, seq)
        moe = None
        if self._moe_counted:
            # [B + 3]: the step's routing counters behind the logprobs
            moe = tuple(int(x) for x in lps_np[self.cfg.max_batch_size:])
        results = []
        for i, st in enumerate(seqs):
            if st is None:
                continue
            if st.req.sampling.logprobs > 0 and tlp_ids_np is not None:
                results.append((st, int(toks_np[i]), float(lps_np[i]),
                                tlp_ids_np[i], tlp_vals_np[i]))
            else:
                results.append(
                    (st, int(toks_np[i]), float(lps_np[i]), None, None)
                )
        return results, moe

    def _run_decode(self, seqs: List[Optional["_Seq"]]) -> List[Tuple[_Seq, int, float]]:
        with loop_span(self, "pack"):
            (positions, seq_lens, write_blocks, write_offsets, steps, _) = (
                self._decode_dispatch_arrays(seqs)
            )
            lp_need = bool(np.any((self._lp_ns > 0) & (seq_lens > 0)))
            g_dev, g_rows = (), {}
            if self.guided_enabled:
                # single-step dispatches are never chained: the host FSM
                # state (walked in _accept_tokens) is authoritative
                g_dev = self._guided_dev()
                g_rows = dict(g_state=self._g_state)
            step = step_args.pack(
                self.cfg.max_batch_size, self._table_width,
                lp_need=lp_need, tokens=self._tokens, positions=positions,
                seq_lens=seq_lens, write_blocks=write_blocks,
                write_offsets=write_offsets, steps=steps, **g_rows,
            )
        with loop_span(self, "upload"):
            args = self._upload((
                self.params, self.k_caches, self.v_caches, self.output_counts,
                step, self._dev("tables", self._block_tables),
                *self._slot_sampling_dev(),
                self.prompt_masks, self._lora_tables(),
                self._dev("lora_slots", self._lora_slots),
                self._dev("proc_masks", self._lp_masks),
                *g_dev,
            ))
        with loop_span(self, "launch"):
            seq, (self.k_caches, self.v_caches, self.output_counts, toks, lps,
                  tlp_vals, tlp_ids) = launch(
                self, self._decode_fn, 1, *args)
            del args  # donated caches: hold no stale handles
            self._count_state(np.count_nonzero(seq_lens), 0, 1)
            self._count_paged(seqs, seq_lens, 1)
        with loop_span(self, "sync"):
            results, self._moe_last = self._decode_results(
                seq, seqs, toks, lps,
                *((tlp_ids, tlp_vals) if lp_need else ())
            )
            self._took(seq)
            return results

    # -- host-side token bookkeeping -----------------------------------------
    def _accept_token(
        self,
        st: _Seq,
        tok: int,
        logprob: float,
        tlp_ids: Optional[np.ndarray] = None,
        tlp_vals: Optional[np.ndarray] = None,
    ) -> None:
        self._accept_tokens(
            st, [tok], [logprob],
            tlp_ids[None] if tlp_ids is not None else None,
            tlp_vals[None] if tlp_vals is not None else None,
        )

    def _accept_tokens(
        self,
        st: _Seq,
        toks: List[int],
        logprobs: List[float],
        tlp_ids: Optional[np.ndarray] = None,   # [N, K]
        tlp_vals: Optional[np.ndarray] = None,  # [N, K]
    ) -> None:
        """Runs in the executor thread: pure host state mutation. Processes a
        run of sampled tokens for one sequence (a decode horizon, or a single
        token) and emits ONE BackendOutput; tokens past a finish are the
        discarded speculative tail."""
        emit_ids: List[int] = []
        emit_lps: List[float] = []
        tlp: Optional[List[Dict[int, float]]] = None
        n_tlp = min(st.req.sampling.logprobs, TOP_LOGPROBS_K)
        if n_tlp > 0 and tlp_ids is not None:
            tlp = []
        finish: Optional[str] = None
        first_ann = st.produced == 0
        stop_ids = set(st.req.stop.stop_token_ids)
        limit = st.req.stop.max_tokens
        cancelled = st.context.is_stopped()

        for n, tok in enumerate(toks):
            st.produced += 1
            # engine-level stop ids only; the worker Backend layer enforces
            # the tokenizer-specific EOS (llm/backend.py)
            if tok in stop_ids and st.produced > st.req.stop.min_tokens:
                finish = FINISH_STOP
                break  # stop token excluded from output
            emit_ids.append(tok)
            emit_lps.append(logprobs[n])
            if tlp is not None:
                tlp.append({
                    int(i): float(v)
                    for i, v in zip(tlp_ids[n][:n_tlp], tlp_vals[n][:n_tlp])
                })
            if limit is not None and st.produced >= limit:
                finish = FINISH_LENGTH
            elif cancelled:
                finish = "cancelled"

            if finish is None:
                L_before = len(st.seq)
                if L_before + 1 >= self.cfg.max_context:
                    finish = FINISH_LENGTH
                else:
                    sealed = st.seq.append(tok)
                    st.last_token = tok
                    if sealed is not None and not st.no_cache:
                        self.allocator.commit(
                            st.block_ids[sealed.position], sealed.sequence_hash
                        )
                        self._commit_windows(
                            st, sealed.position, sealed.sequence_hash
                        )
                        if self.kvbm is not None:
                            self._offload_pending.append(
                                (st.block_ids[sealed.position], sealed.sequence_hash, 1)
                            )
                    # ensure a block exists for the NEXT token's write position
                    needed_blocks = (L_before + 1) // self.cfg.block_size + 1
                    if self._ring is not None:
                        # a whole ring at most; the next token may open a
                        # window, which takes its summary block now
                        needed_blocks, windows = self._ring.held(L_before + 2)
                        if not self._take_summary_blocks(st, windows):
                            finish = FINISH_LENGTH
                    if self._win_groups and self._slide(
                        st, len(st.seq) - 1, L_before + 2
                    ) is None:
                        finish = FINISH_LENGTH  # out of memory: end gracefully
                    if needed_blocks > len(st.block_ids):
                        try:
                            (new_id,) = self.allocator.allocate(1)
                            st.block_ids.append(new_id)
                            self._block_tables[st.slot, len(st.block_ids) - 1] = new_id
                        except OutOfBlocks:
                            finish = FINISH_LENGTH  # out of memory: end gracefully
            if finish is not None:
                break

        if st.guided_tables is not None and emit_ids:
            # host replay of the device FSM over the tokens that survived
            # stop handling: authoritative for the next unchained dispatch.
            # Device-sampled tokens are always legal under the mask, so a
            # step failure means table corruption — fail the request, not
            # the engine loop.
            try:
                st.guided_state = st.guided_tables.walk(
                    st.guided_state, emit_ids
                )
                if 0 <= st.slot < len(self._g_state):
                    self._g_state[st.slot] = st.guided_state
            except ValueError:
                log.exception("guided FSM desync")
                finish = FINISH_ERROR

        ann: Dict[str, Any] = {}
        if first_ann:
            ann = {
                "cached_tokens": st.cached_tokens,
                "input_tokens": len(st.req.token_ids),
            }
            # echo the router's routing decision back on the metrics frame
            # (protocols/common.py documents worker_id as a first-chunk
            # annotation) so the frontend's flight record can attribute the
            # request to the worker that actually served it
            wid = (st.req.annotations or {}).get("worker_id")
            if wid is not None:
                ann["worker_id"] = wid
        if first_ann and (emit_ids or finish is not None) and st.t_first_token == 0:
            st.t_first_token = now_ns()
            get_flight_recorder().record(
                st.req.request_id, "first_token", slot=st.slot,
            )
        out = BackendOutput(
            token_ids=emit_ids,
            finish_reason=finish,
            cumulative_tokens=st.produced,
            logprobs=emit_lps if emit_ids else None,
            top_logprobs=tlp if (tlp and emit_ids) else None,
            annotations=ann,
        )
        st.out_queue.put_nowait(out)
        if finish is not None:
            st.done = True

    def _reap_finished(self) -> None:
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            if st.done or st.context.is_killed():
                self._release(st)
                self._slots[i] = None
                self._seq_lens[i] = 0
                if self.guided_enabled and self._g_active[i]:
                    # freed slot must not mask the next occupant's first
                    # dispatch (admission overwrites the tables, but a
                    # non-guided successor would otherwise inherit them)
                    self._g_active[i] = False
                    self._g_active_version += 1
                if not st.done:
                    st.out_queue.put_nowait(
                        BackendOutput(finish_reason="cancelled", cumulative_tokens=st.produced)
                    )

    def _request_finished(self, st: "_Seq", finish_reason: str) -> None:
        """Emit the request's engine-phase spans (queue / prefill / decode,
        parented on the cross-plane traceparent annotation) and close its
        flight-recorder timeline. Host-side bookkeeping only."""
        flight = get_flight_recorder()
        rid = st.req.request_id
        if st.sla is not None:
            self._slo_finished(st, finish_reason)
        flight.finish(
            rid,
            error=("engine error finish" if finish_reason == FINISH_ERROR else None),
            error_class="engine_error" if finish_reason == FINISH_ERROR else None,
            finish_reason=finish_reason,
            tokens=st.produced,
            **({"sla_class": st.sla.sla_class} if st.sla is not None else {}),
        )
        # critical-path attribution (runtime/attribution.py): fold the
        # closed timeline into the worker's rolling per-(model, class)
        # phase aggregates — the /debug/worker "where does p99 go" view
        try:
            timeline = flight.timeline(rid)
            if timeline is not None:
                get_attribution().observe_flight(
                    st.req.model,
                    st.sla.sla_class if st.sla is not None else "unclassified",
                    timeline,
                )
        except Exception:
            log.exception("attribution observe failed for %s", rid[:8])
        tracer = get_tracer()
        if not tracer.enabled:
            return
        tp = st.req.annotations.get("traceparent")
        status = "ERROR" if finish_reason == FINISH_ERROR else "OK"
        unix = self._unix_ns
        if st.t_queued and st.t_admitted:
            tracer.emit(
                "engine.queue", unix(st.t_queued), unix(st.t_admitted),
                traceparent=tp, request_id=rid,
            )
        prefill_start = st.t_prefill_start or st.t_admitted
        if prefill_start and st.t_first_token:
            tracer.emit(
                "engine.prefill", unix(prefill_start), unix(st.t_first_token),
                traceparent=tp, request_id=rid,
                prompt_tokens=len(st.req.token_ids),
                cached_tokens=st.cached_tokens,
            )
        if st.t_first_token:
            tracer.emit(
                "engine.decode", unix(st.t_first_token), unix(now_ns()),
                traceparent=tp, request_id=rid, status=status,
                tokens=st.produced, finish=finish_reason,
            )

    def _slo_finished(self, st: "_Seq", finish_reason: str) -> None:
        """Feed the worker-side SLO ledger from the milestone timestamps the
        loop already stamped (host-side scalars — no device sync). TTFT is
        anchored on the frontend receipt stamp riding the sla annotation
        when present (same-host wall clock), else on engine queue entry;
        ITL is the request's mean decode gap."""
        spec = st.sla
        now = now_ns()
        # the frontend's receipt stamp is unix ns: onto the stamps' clock
        t0 = sla_t0_ns(st.req.annotations)
        t0 = t0 - self._wall_offset_ns if t0 else st.t_queued
        ttft_s = (
            (st.t_first_token - t0) / 1e9 if st.t_first_token else None
        )
        itl_s = None
        if st.t_first_token and st.produced > 1:
            itl_s = (now - st.t_first_token) / 1e9 / (st.produced - 1)
        e2e_s = (now - t0) / 1e9
        met = get_slo_accountant().record(
            st.req.model, spec,
            ttft_s=ttft_s, itl_s=itl_s,
            output_tokens=st.produced, e2e_s=e2e_s,
        )
        fields: Dict[str, Any] = dict(
            sla_class=spec.sla_class,
            met=met,
            ttft_ms=(None if ttft_s is None else round(ttft_s * 1e3, 3)),
            ttft_target_ms=round(spec.ttft_target_s * 1e3, 3),
            itl_ms=(None if itl_s is None else round(itl_s * 1e3, 3)),
            itl_target_ms=round(spec.itl_target_s * 1e3, 3),
        )
        if spec.deadline_s > 0:
            fields["deadline_remaining_s"] = round(spec.deadline_s - e2e_s, 3)
        if not met or finish_reason == FINISH_ERROR:
            get_flight_recorder().record(
                st.req.request_id, "slo_violation", **fields
            )

    def _count_state(self, row_steps: int, chunk_tokens: int, steps: int) -> None:
        """A family with slot state (nothing for any other): what a dispatch
        advanced, for the next StepStats, under the family's prefix
        (``<ssm|kda>_rows_updated``: ``row_steps``, one for each live row of
        each of its ``steps``, a STATE layer; ``_tokens_scanned``;
        ``_decode_steps``): host arithmetic on the step's own shapes."""
        if self.state is None:
            return
        c, L = self._state_counts, self.state.num_layers
        c[0] += int(row_steps) * L
        c[1] += chunk_tokens * L
        c[2] += steps

    def _count_paged(self, seqs, contexts, steps: int) -> None:
        """Decode rows of a dispatch whose attention is the decode-only
        kernel's in some layers (nothing where no layer's is): row ``i`` of
        ``seqs`` attended over ``contexts[i] + k`` keys in step ``k`` of
        ``steps``. Counts the whole chunks of pages under those contexts and
        those of them that are runs of consecutive block ids, by the kernel's
        own chunk rule (ops/pallas_paged.py), x the layers that launch it,
        for the next StepStats: host arithmetic on ids the host holds. A
        request's runs are looked at once a chunk, as it fills."""
        if not self._paged_layers:
            return
        cp = next(iter(self._paged_layers.values()))
        T, bs = cp * self.cfg.block_size, self.cfg.block_size
        whole = run = 0
        for st, ctx in zip(seqs, contexts):
            if st is None or ctx <= 0:
                continue
            # step k attends over ctx + k keys: (ctx + k + bs - 1) // T whole
            # chunks, of the pages the request holds
            first, held = int(ctx) + bs - 1, len(st.block_ids) // cp
            cum = st.run_chunks
            for w in range(first // T, (first + steps - 1) // T + 1):
                n = min(first + steps, (w + 1) * T) - max(first, w * T)
                w = min(w, held)
                while len(cum) <= w:
                    ids = st.block_ids[(len(cum) - 1) * cp : len(cum) * cp]
                    cum.append(cum[-1] + all(
                        b - a == 1 for a, b in zip(ids, ids[1:])))
                whole += w * n
                run += cum[w] * n
        layers = len(self._paged_layers)
        self._paged_counts[0] += whole * layers
        self._paged_counts[1] += run * layers

    def _step_stats(self, phase: str, duration_s: float, tokens: int,
                    link: Optional[_Chain] = None) -> None:
        """Feed one StepStats to the hook — scalars the loop already holds;
        never forces a device sync (engine/telemetry.py). ``link``: the
        mixed step this one is, read now and launched a tick ago."""
        if link is not None:
            # its own dispatch's: the next link's are already being counted
            placed = link.placed
        else:
            placed, self._h2d_placements = self._h2d_placements, 0
        hook = self.stats_hook
        if hook is None:
            return
        spec_acc = None
        if self.cfg.spec_draft is not None and self.spec_stats["rounds"]:
            spec_acc = self.spec_stats["emitted"] / (
                self.spec_stats["rounds"] * self.spec_stats["k"]
            )
        # async step-prep accounting: only chunk-carrying phases consume a
        # prebuild (engine/prep.py take(); a mixed link popped its own at
        # its launch, a tick before it is read)
        if link is not None:
            prep = link.prep_hit
        elif self._prep is not None and phase == "prefill":
            prep = self._prep.pop_last()
        else:
            prep = None
        # what the host did since the last StepStats (popleft, not a copy
        # and clear: the deques lose nothing to a concurrent append, and
        # the spans, three values each, stay whole)
        spans, waits = self._host_spans, self._admit_waits
        host_spans = tuple(spans.popleft() for _ in range(len(spans)))
        admit_wait_s = tuple(waits.popleft() for _ in range(len(waits)))
        # a whole number of spans: every extend adds four values
        rspans = self._request_spans
        request_spans = tuple(rspans.popleft() for _ in range(len(rspans)))
        # the launch ledger: whole records too (seven values, two values)
        made, landed = self._launches, self._arrivals
        launches = tuple(made.popleft() for _ in range(len(made)))
        arrivals = tuple(landed.popleft() for _ in range(len(landed)))
        # set by the step's own readback; a prefill-only step has none
        routed, touched, load_max, *reads = self._moe_last or (None,) * 3
        if not registry.counts_routing(self.mcfg):
            # a family that rides the readback for its read counters alone
            routed = touched = load_max = None
        # behind them what a latent's decode rows read, by StepStats' names
        reads = dict(zip(self._read_counters, reads))
        held = getattr(self.mcfg, "experts_held", None) is not None
        self._moe_last = None
        occupancy = sum(1 for s in self._slots if s is not None and not s.done)
        if self.state is not None:
            rows, scanned, steps = self._state_counts
            self._state_counts = [0, 0, 0]
            pre = self._state_prefix
            reads.update({
                f"{pre}_rows_updated": rows, f"{pre}_tokens_scanned": scanned,
                f"{pre}_decode_steps": steps, "state_prefix": pre,
                # the slot store's bytes, whatever recurrence fills it
                "ssm_state_bytes": occupancy * self.state.bytes_per_slot,
            })
        if self._paged_layers:
            reads["paged_chunks_whole"], reads["paged_chunks_run"] = (
                self._paged_counts)
            self._paged_counts = [0, 0]
        if self._win_groups:
            # pages by layer kind: what the live rows hold a group, what
            # their windows let go since the last StepStats
            live = [s for s in self._slots if s is not None and not s.done]
            reads["page_groups_held"] = (
                sum(len(s.block_ids) for s in live),
                *(sum(len(s.win_ids[g]) for s in live if s.win_ids)
                  for g in range(len(self._win_groups))),
            )
            reads["page_groups_released"] = (
                0, *(grp.released for grp in self._win_groups)
            )
            for grp in self._win_groups:
                grp.released = 0
        try:
            hook(StepStats(
                phase=phase,
                duration_s=duration_s,
                batch_occupancy=occupancy,
                batch_size=self.cfg.max_batch_size,
                tokens=int(tokens),
                queue_depth=len(self._waiting),
                kv_active_blocks=self.allocator.active_blocks,
                kv_free_blocks=self.allocator.free_blocks,
                kv_total_blocks=self.cfg.num_blocks,
                spec_acceptance=spec_acc,
                prep_hit=prep,
                host_spans=host_spans,
                admit_wait_s=admit_wait_s,
                request_spans=request_spans,
                launches=launches,
                arrivals=arrivals,
                moe_tokens_routed=routed,
                moe_experts_touched=touched,
                moe_load_max=load_max,
                moe_held_experts_touched=touched if held else None,
                **reads,
                h2d_placements=placed,
                mixed_chained=None if link is None else link.chained,
            ))
        except Exception:
            log.exception("stats hook failed")

    async def _publish_events(self) -> None:
        stored, removed = self.allocator.drain_events()
        if self.kvbm is not None:
            # tier evictions: blocks gone from G2+G3 AND not resident in G1
            # are no longer servable anywhere -> tell the router
            gone = [
                h for h in self.kvbm.drain_evicted()
                if self.allocator._by_hash.get(h) is None
            ]
            if gone:
                removed = removed + [gone]
            if self.kv_directory is not None:
                # fleet directory upkeep rides the same consolidated cadence:
                # advertise fresh tier offloads, withdraw what no tier holds.
                # Best-effort — a directory-plane wobble (or armed
                # directory.publish fault) must never stall the event loop;
                # the TTL lease ages out anything a failed withdraw left
                try:
                    fresh = self.kvbm.drain_stored()
                    by_tier: Dict[str, List[int]] = {}
                    for h in fresh:
                        t = self.kvbm.tier_of(h)
                        if t is not None:
                            by_tier.setdefault(t, []).append(h)
                    fmt = "int8" if self.kv_quantized else "model"
                    for t, hs in sorted(by_tier.items()):
                        await self.kv_directory.publish(hs, t, fmt)
                    if gone:
                        await self.kv_directory.unpublish(gone)
                except Exception:
                    log.warning(
                        "kv directory upkeep failed (continuing)",
                        exc_info=True,
                    )
            # a device-evicted block still in G2/G3/G4 is still servable (we
            # onboard on demand): don't tell the router it's gone — the
            # consolidated view, like the reference's kv_consolidator
            # (lib/llm/src/block_manager/kv_consolidator). Remote membership
            # is one batched RPC per event batch, off the event loop (the G4
            # socket blocks; same treatment as match_prefix above).
            loop_ = asyncio.get_event_loop()
            filtered = []
            for batch in removed:
                servable = set(await loop_.run_in_executor(
                    None, self.kvbm.filter_servable, batch
                ))
                gone_batch = [h for h in batch if h not in servable]
                if gone_batch:
                    filtered.append(gone_batch)
            removed = filtered
        if self.kv_publisher is not None:
            for batch in stored:
                await self.kv_publisher.stored(batch)
            for batch in removed:
                await self.kv_publisher.removed(batch)
        if self.metrics_publisher is not None:
            # publish on KV events AND whenever load changed: releases emit
            # no events (blocks just move to the reusable cache), and a
            # stale active-block report would leave the router seeing
            # phantom load on an idle worker
            running = sum(
                1 for s in self._slots if s is not None and not s.done
            )
            load = (self.allocator.active_blocks, len(self._waiting), running)
            if stored or removed or load != self._last_published_load:
                self._last_published_load = load
                await self.metrics_publisher.publish(
                    active_decode_blocks=load[0],
                    num_requests_waiting=load[1],
                    num_requests_active=running,
                    total_blocks=self.cfg.num_blocks,
                )

    # -- introspection -------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        snap = {
            "running": sum(1 for s in self._slots if s is not None),
            "waiting": len(self._waiting),
            "active_blocks": self.allocator.active_blocks,
            "cached_blocks": self.allocator.cached_blocks,
            "free_blocks": self.allocator.free_blocks,
            # what this engine computes on and which attention path it
            # resolved — a launcher that must not touch JAX itself
            # (chip_smoke.py) reads them from the worker's /metadata
            "device": device_info(),
            "device_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in self.mesh.devices.flat
                if d.process_index == jax.process_index()
            ],
            "use_pallas": self.use_pallas,
            "mixed_enabled": self.mixed_enabled,
            "kernels_interpreted": self.kernels_interpreted,
            "decode_steps": self.cfg.decode_steps,
            "decode_pipeline": self.cfg.decode_pipeline,
        }
        if self.state is not None:
            # the second kind of state: bytes held a slot and in all
            snap["slot_state"] = {
                "bytes_per_slot": self.state.bytes_per_slot,
                "bytes": self.state.nbytes,
                "slots": self.state.slots,
            }
        if self._ring is not None:
            # the third kind of state: the ring's geometry and the store of
            # summary blocks beside the ring pages
            snap["ring"] = {
                "positions": self._ring.positions,
                "pages": self._ring.pages,
                "summary_blocks": self.summary_allocator.num_blocks - 1,
                "summary_blocks_free": self.summary_allocator.free_blocks,
            }
        if self._win_groups:
            # pages by layer kind: each windowed group's geometry and pool
            snap["page_groups"] = [
                {
                    "layers": list(g.layers), "window": g.window,
                    "table_pages": g.pages,
                    "blocks": g.allocator.num_blocks - 1,
                    "active_blocks": g.allocator.active_blocks,
                    "cached_blocks": g.allocator.cached_blocks,
                    "free_blocks": g.allocator.free_blocks,
                }
                for g in self._win_groups
            ]
        if self.cfg.spec_draft is not None:
            snap["spec"] = dict(self.spec_stats)
        if self._eplb_enabled:
            snap["eplb"] = {
                "redundant_experts": self.mcfg.redundant_experts,
                "physical_experts": self.mcfg.num_physical_experts,
            }
        if self.kvbm is not None:
            snap["kvbm"] = {
                "g2_blocks": len(self.kvbm.host),
                "g3_blocks": len(self.kvbm.disk) if self.kvbm.disk is not None else 0,
                "offloaded": self.kvbm.offloaded,
                "onboarded": self.kvbm.onboarded,
            }
        return snap

    async def clear_kv_blocks(self, levels: Optional[List[str]] = None) -> Dict[str, Any]:
        """Runtime cache reset (reference block_manager/controller.rs
        cache-level commands + http/clear_kv_blocks.rs): drop the device
        prefix cache (g1) and/or the KVBM offload tiers (g2 host, g3 disk).
        Active requests keep their pinned blocks — only reusable cache is
        dropped. The router view stays honest: a g1 clear publishes a
        wholesale CLEARED event for this worker; tier clears ride the
        consolidated removed-event path."""
        if levels is not None and (
            not isinstance(levels, (list, tuple))
            or any(not isinstance(lv, str) for lv in levels)
        ):
            raise ValueError("levels must be a list of tier names")
        # None = clear everything; an explicit empty list clears nothing
        # (same semantics as the mocker)
        levels = [
            lv.lower()
            for lv in (levels if levels is not None else ["g1", "g2", "g3"])
        ]
        result: Dict[str, Any] = {}
        if "g1" in levels:
            before = self.allocator.cached_blocks
            self.allocator.clear()
            for grp in self._win_groups:
                grp.allocator.clear()
            # clear() intentionally emits no per-hash events (comment there):
            # the wholesale CLEARED event resets this worker in the indexer
            if self.kv_publisher is not None:
                await self.kv_publisher.cleared()
            result["g1"] = before
        if self.kvbm is not None and ("g2" in levels or "g3" in levels):
            counts = self.kvbm.clear(
                host="g2" in levels, disk="g3" in levels
            )
            result.update({k: v for k, v in counts.items() if k in levels})
            # push the eviction notifications out now, not at the next step
            await self._publish_events()
        result["snapshot"] = self.snapshot()
        return result
