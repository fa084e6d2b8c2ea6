"""PrefillRouter: disaggregated prefill/decode orchestration on the frontend.

Analog of the reference's PrefillRouter (lib/llm/src/kv_router/
prefill_router.rs:102,505 + docs/design_docs/disagg_serving.md): when a
prefill pool is registered for a model, each request is first sent to a
prefill worker as a clone with ``max_tokens=1``; the first token streams to
the client immediately, and the decode request carries the prefill worker's
KV-transfer metadata (address + block hashes) plus the first token as prior
context. If no prefill pool exists (elastic xPyD: pools scale to zero) the
request falls through to the aggregated path — runtime-reconfigurable
disaggregation, like the reference (disagg_serving.md:67-69).

Three disagg-era behaviors layer on top (``DisaggConfig``):

- **transfer-cost-aware selection** (NetKV-style): every prefill candidate's
  logit carries the estimated seconds to ship the request's KV over that
  candidate's advertised wire class (per-wire EWMA bandwidth from
  ``runtime/bandwidth.py``, observed on real ``kv.transfer.pull`` legs),
  normalized into the scheduler's block units — a candidate behind a slow
  wire loses to one a device hop away at equal queue depth.
- **prefill deflection** (load-aware): short prompts, requests whose prefix
  is already hot in the DECODE pool's radix tree, and requests whose best
  disagg plan costs more than ``deflect_margin``x the local prefill skip
  the hop entirely and prefill on the decode worker (mixed continuous
  batching makes the deflected chunk ride the decode dispatch).
- **streamed dispatch**: when the chosen prefill worker advertises its
  transfer address in instance metadata, the decode request ships
  IMMEDIATELY with a streamed ``kv_transfer`` handshake — its block-window
  pull overlaps the prefill compute instead of serializing behind it.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

from ..kv_router import KvRouter, KvRouterConfig, WorkerWithDpRank
from ..runtime import metrics as M
from ..runtime.bandwidth import get_bandwidth_estimator
from ..runtime.component import Client, RouterMode
from ..runtime.engine import Context
from ..runtime.errors import is_terminal
from ..runtime.flight_recorder import get_flight_recorder
from ..runtime.logging import get_logger
from ..runtime.request_plane.tcp import NoResponders
from ..runtime.tasks import spawn_bg
from ..runtime.tracing import get_tracer
from ..tokens import compute_sequence_hashes
from .model_card import ModelDeploymentCard
from .preprocessor import ANNOTATION_PREFILL_WORKER_ID
from .protocols.common import BackendOutput, PreprocessedRequest

log = get_logger("llm.prefill_router")

# fallback KV footprint when neither the config nor the card advertises one:
# a mid-size bf16 model's order of magnitude (the estimate only has to rank
# wires, not bill them)
_DEFAULT_KV_BYTES_PER_BLOCK = 256 * 1024


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


@dataclasses.dataclass
class DisaggConfig:
    """Knobs for streamed disagg, transfer-cost-aware routing and prefill
    deflection (env-overridable; docs/operations.md 'Disaggregation')."""

    # streamed decode dispatch (DTPU_STREAM_KV=0 restores the sequential
    # prefill -> transfer -> decode pipeline)
    streamed: bool = True
    # deflection master switch (DTPU_DEFLECT=0 -> every request pays the hop)
    deflect: bool = True
    # prompts at or under this many tokens never take the disagg hop: the
    # handshake + wire tail exceeds what their prefill costs locally
    deflect_max_tokens: int = 128
    # deflect when the decode pool already holds at least this fraction of
    # the prompt's blocks (radix-hot prefix: shipping KV it has is waste)
    deflect_overlap_frac: float = 0.5
    # deflect when the best disagg plan's cost (queue + prefill + wire, in
    # block units) exceeds (1 + margin) x the local prefill cost — the
    # load-skew valve: deep prefill queues push traffic back to decode
    deflect_margin: float = 1.0
    # seconds to prefill one KV block, used to convert wire seconds into
    # the scheduler's block-unit logits (coarse; DTPU_PREFILL_BLOCK_MS)
    prefill_block_time_s: float = 0.010
    # override the per-block wire bytes (0 = card's advertised value)
    kv_bytes_per_block: int = 0

    @classmethod
    def from_env(cls) -> "DisaggConfig":
        return cls(
            streamed=os.environ.get("DTPU_STREAM_KV", "1") != "0",
            deflect=os.environ.get("DTPU_DEFLECT", "1") != "0",
            deflect_max_tokens=int(
                _env_f("DTPU_DEFLECT_MAX_TOKENS", cls.deflect_max_tokens)
            ),
            deflect_overlap_frac=_env_f(
                "DTPU_DEFLECT_OVERLAP", cls.deflect_overlap_frac
            ),
            deflect_margin=_env_f("DTPU_DEFLECT_MARGIN", cls.deflect_margin),
            prefill_block_time_s=_env_f("DTPU_PREFILL_BLOCK_MS", 10.0) / 1e3,
            kv_bytes_per_block=int(_env_f("DTPU_KV_BYTES_PER_BLOCK", 0)),
        )


@dataclasses.dataclass
class PrefillPlan:
    """One routing decision for the disagg hop (or the decision to skip it).

    ``deflect_reason`` set => serve aggregated. Otherwise ``worker_id``
    names the prefill worker; ``transfer_address`` (from its instance
    metadata) non-None + ``streamed`` => early decode dispatch with a
    streamed kv_transfer handshake."""

    deflect_reason: Optional[str] = None
    worker_id: Optional[int] = None
    dp_rank: int = 0
    overlap_blocks: int = 0
    query_blocks: int = 0
    transfer_address: Optional[str] = None
    wire: str = "inline"
    streamed: bool = False
    est_transfer_s: float = 0.0
    hashes: List[int] = dataclasses.field(default_factory=list)

    @property
    def deflected(self) -> bool:
        return self.deflect_reason is not None


class GlobalKvFetchPlanner:
    """Fleet-wide KV reuse planning on the frontend (kvbm/directory.py).

    On a local radix miss, the missing prefix may be sealed in some OTHER
    worker's G2/G3 tier. This planner looks the miss up in the global block
    directory, prices onboard-from-peer-tier against recompute
    (``ops/costs.fetch_vs_recompute``, fed by the same wire-bandwidth EWMA
    the disagg hop prices with plus the holder tier's read latency), and —
    when fetching wins — returns a ``kv_transfer`` plan (``tier=True``)
    that streams the blocks from the holder over the block-window protocol
    instead of re-prefilling them. Directory staleness, a dead holder or a
    mid-fetch loss all degrade to recompute on the worker (engine-side
    fallback); the plan is advisory, never load-bearing for correctness."""

    # the tier wire class the fetch path observes into the bandwidth EWMA
    # (engine/transfer.py _pull_tier); unseen it prices at the inline prior
    WIRE = "tier"

    def __init__(
        self,
        directory,
        *,
        block_size: int,
        kv_bytes_per_block: int = 0,
        prefill_block_time_s: float = 0.010,
        prefill_base_s: float = 0.0,
        margin: Optional[float] = None,
        min_run_blocks: int = 1,
        bandwidth=None,
    ):
        from ..kvbm.directory import fetch_margin

        self.directory = directory
        self.block_size = int(block_size)
        self.kv_bytes_per_block = int(
            kv_bytes_per_block or _DEFAULT_KV_BYTES_PER_BLOCK
        )
        self.prefill_block_time_s = float(prefill_block_time_s)
        self.prefill_base_s = float(prefill_base_s)
        self.margin = float(margin if margin is not None else fetch_margin())
        self.min_run_blocks = max(1, int(min_run_blocks))
        self.bandwidth = bandwidth or get_bandwidth_estimator()

    def price(self, num_blocks: int, tier: str = "g2") -> Dict:
        """The fetch-vs-recompute verdict for ``num_blocks`` missing blocks
        (ops/costs.fetch_vs_recompute, tier-1 grid-gated)."""
        from ..ops.costs import fetch_vs_recompute

        return fetch_vs_recompute(
            num_blocks,
            block_size=self.block_size,
            kv_bytes_per_block=self.kv_bytes_per_block,
            bandwidth_bytes_s=self.bandwidth.bandwidth(self.WIRE),
            prefill_base_s=self.prefill_base_s,
            prefill_per_token_s=self.prefill_block_time_s / self.block_size,
            tier=tier,
            margin=self.margin,
        )

    async def plan_fetch(
        self,
        req: PreprocessedRequest,
        hashes: List[int],
        overlap_blocks: int,
        exclude_holder: Optional[str] = None,
    ) -> Optional[Dict]:
        """Return a ``kv_transfer`` plan dict for the request's missing
        prefix, or None to recompute. ``overlap_blocks`` is the decode
        pool's best local radix overlap (those blocks never fetch);
        ``hashes`` must be at this planner's block size."""
        miss = [int(h) for h in hashes[overlap_blocks:]]
        if len(miss) < self.min_run_blocks:
            return None
        run = await self.directory.lookup_run(
            miss, exclude_holder=exclude_holder
        )
        if len(run) < self.min_run_blocks:
            return None  # nobody (live) holds the prefix: plain recompute
        head = run[0]
        verdict = self.price(len(run), tier=head.tier)
        get_flight_recorder().record(
            req.request_id, "global_kv_plan",
            holder=head.holder, tier=head.tier, blocks=len(run),
            fetch_s=round(verdict["fetch_s"], 6),
            recompute_s=round(verdict["recompute_s"], 6),
            fetch_wins=verdict["fetch_wins"],
        )
        if not verdict["fetch_wins"] or not head.address:
            # the directory HAD the prefix but recompute prices cheaper
            # (or the holder advertises no fetch endpoint)
            self.directory.record_outcome("recomputed")
            return None
        return {
            "address": head.address,
            "hashes": [e.hash for e in run],
            "num_tokens": len(run) * self.block_size,
            "tier": True,
            "holder": head.holder,
            "est_fetch_s": verdict["fetch_s"],
        }


class PrefillRouter:
    def __init__(
        self,
        runtime,
        card: ModelDeploymentCard,
        kv_router_config: Optional[KvRouterConfig] = None,
        disagg: Optional[DisaggConfig] = None,
    ):
        self.runtime = runtime
        self.card = card  # the *prefill* pool's card
        self.client: Optional[Client] = None
        self.kv_router: Optional[KvRouter] = None
        self.kv_router_config = kv_router_config
        self.disagg = disagg or DisaggConfig.from_env()
        self.bandwidth = get_bandwidth_estimator()
        metrics = getattr(runtime, "metrics", None)
        self._deflected = (
            metrics.counter(
                M.PREFILL_DEFLECTED_TOTAL,
                "requests that skipped the disagg prefill hop",
                extra_labels=("reason",),
            )
            if metrics is not None else None
        )
        if metrics is not None:
            # frontend processes: expose the per-wire EWMA this router
            # prices candidates with (workers attach in engine/__main__)
            self.bandwidth.attach_metrics(metrics)

    async def start(self) -> "PrefillRouter":
        endpoint = (
            self.runtime.namespace(self.card.namespace)
            .component(self.card.component)
            .endpoint(self.card.endpoint)
        )
        self.client = await endpoint.client(RouterMode.ROUND_ROBIN)
        if self.kv_router_config is not None:
            self.kv_router = await KvRouter(
                self.runtime.event_plane,
                self.card.namespace,
                self.card.component,
                block_size=self.card.kv_block_size,
                config=self.kv_router_config,
                metrics=getattr(self.runtime, "metrics", None),
            ).start()
        return self

    @property
    def has_workers(self) -> bool:
        return self.client is not None and bool(self.client.instances)

    # -- transfer-cost-aware planning + deflection ---------------------------
    def _kv_bytes_per_block(self) -> int:
        if self.disagg.kv_bytes_per_block > 0:
            return self.disagg.kv_bytes_per_block
        adv = int(getattr(self.card.runtime_config, "kv_bytes_per_block", 0) or 0)
        return adv or _DEFAULT_KV_BYTES_PER_BLOCK

    def _candidates(self) -> List[WorkerWithDpRank]:
        cands: List[WorkerWithDpRank] = []
        if self.client is None:
            return cands
        # dp-aware like the decode path (scheduler.rs:543-560): every
        # (instance, dp_rank) is a candidate, and the chosen rank rides the
        # annotation so the worker's DpEngineGroup dispatches to it
        for iid, inst in self.client.instances.items():
            dp = int(inst.metadata.get("data_parallel_size", 1) or 1)
            for r in range(dp):
                cands.append(WorkerWithDpRank(iid, r))
        return cands

    def _instance_meta(self, iid: int, key: str):
        inst = self.client.instances.get(iid) if self.client else None
        return inst.metadata.get(key) if inst is not None else None

    def _record_deflect(self, req: PreprocessedRequest, reason: str) -> PrefillPlan:
        get_flight_recorder().record(
            req.request_id, "prefill_deflected", reason=reason
        )
        if self._deflected is not None:
            self._deflected.inc(reason=reason)
        log.debug("deflecting %s (%s)", req.request_id[:8], reason)
        return PrefillPlan(deflect_reason=reason)

    def plan(
        self, req: PreprocessedRequest, decode_overlap_blocks: int = 0,
        hashes: Optional[List[int]] = None,
    ) -> Optional[PrefillPlan]:
        """Price the disagg hop for this request: deflect it, or pick the
        prefill worker whose (queue + remaining prefill + wire) cost is
        lowest. ``decode_overlap_blocks`` is how much of the prompt the
        decode pool's radix tree already holds (those blocks never ship);
        ``hashes`` shares a caller's hash pass (must match this card's
        block size). Returns None when the pool has no candidates (caller
        falls through to aggregated, same as before).

        Scoring is side-effect-free (``score_tokens``); the router's
        optimistic load / approx-index bookkeeping is committed only when
        the request actually takes the hop — a deflected request must not
        leave phantom route state on the prefill pool."""
        cfg = self.disagg
        cands = self._candidates()
        if not cands:
            return None
        tokens = list(req.token_ids)
        block_size = self.card.kv_block_size
        from ..models.vision import IMAGE_TOKEN_ID

        # image placeholder runs hash identically across different images:
        # their blocks are never servable from cache, so neither the
        # overlap estimate nor a streamed handshake may trust the hashes
        # (the prefill engine marks them no_cache and never commits them —
        # a streamed decode pull would stall out waiting)
        cacheable = IMAGE_TOKEN_ID not in tokens
        if hashes is None:
            hashes = compute_sequence_hashes(tokens, block_size)
        query_blocks = max(len(tokens) // block_size, 0)
        if cfg.deflect:
            if len(tokens) <= cfg.deflect_max_tokens:
                return self._record_deflect(req, "short_prompt")
            if (
                cacheable
                and query_blocks > 0
                and decode_overlap_blocks
                >= cfg.deflect_overlap_frac * query_blocks
            ):
                return self._record_deflect(req, "radix_hit")
        # per-candidate wire cost in block units: bytes that must ship over
        # the candidate's advertised wire class, at the EWMA bandwidth
        move_blocks = max(query_blocks - decode_overlap_blocks, 0)
        move_bytes = move_blocks * self._kv_bytes_per_block()
        wires: Dict[WorkerWithDpRank, str] = {}
        extra: Dict[WorkerWithDpRank, float] = {}
        for cand in cands:
            wire = str(self._instance_meta(cand.worker_id, "kv_wire") or "inline")
            wires[cand] = wire
            extra[cand] = (
                self.bandwidth.transfer_seconds(wire, move_bytes)
                / cfg.prefill_block_time_s
            )
        decision = None
        if self.kv_router is not None:
            decision = self.kv_router.score_tokens(
                tokens, cands, extra_costs=extra,
                hashes=hashes if cacheable else [],
            )
            chosen = decision.worker
            overlap = decision.overlap_blocks
            remote_cost = decision.logits[chosen]
        else:
            # round-robin pools still price the wire: cheapest wire wins
            chosen = min(cands, key=lambda c: (extra[c], c))
            overlap = 0
            remote_cost = query_blocks + extra[chosen]
        wire = wires[chosen]
        est_transfer_s = self.bandwidth.transfer_seconds(wire, move_bytes)
        if cfg.deflect:
            # load-aware valve: the hop must beat (1+margin)x local prefill
            local_cost = max(query_blocks - decode_overlap_blocks, 1)
            if remote_cost > (1.0 + cfg.deflect_margin) * local_cost:
                return self._record_deflect(req, "load_skew")
        if decision is not None:
            # taking the hop: NOW commit the route bookkeeping the scoring
            # pass deliberately skipped
            self.kv_router.commit_route(
                decision, hashes if cacheable else []
            )
        address = self._instance_meta(chosen.worker_id, "transfer_address")
        # streamed dispatch only targets rank 0: the transfer server serves
        # engines[0]'s cache, so a dp_rank>0 clone's blocks would never
        # appear on the advertised address and the decode pull would stall
        # out its wait budget before recomputing
        streamed = bool(
            cfg.streamed and address and cacheable and chosen.dp_rank == 0
        )
        return PrefillPlan(
            worker_id=chosen.worker_id,
            dp_rank=chosen.dp_rank,
            overlap_blocks=overlap,
            query_blocks=query_blocks,
            transfer_address=address if streamed else None,
            wire=wire,
            streamed=streamed,
            est_transfer_s=est_transfer_s,
            hashes=[int(h) for h in hashes[:query_blocks]] if cacheable else [],
        )

    def _prefill_clone(self, req: PreprocessedRequest) -> PreprocessedRequest:
        preq = PreprocessedRequest.from_obj(req.to_obj())
        preq.stop.max_tokens = 1
        preq.stop.min_tokens = 0
        preq.stop.stop_strings = []
        preq.annotations["disagg"] = "prefill"
        return preq

    def start_streamed_prefill(
        self, req: PreprocessedRequest, context: Context, plan: PrefillPlan
    ):
        """Fire the max_tokens=1 prefill clone WITHOUT waiting for it: the
        caller dispatches the decode request immediately with a streamed
        kv_transfer handshake, so the decode side's block-window pull
        overlaps this prefill's compute. The clone's sampled token is
        dropped (the decode worker samples the first token itself from the
        imported KV); its only job is producing the KV blocks. Returns the
        background task (bounded: max_tokens=1 finishes on its own)."""
        preq = self._prefill_clone(req)
        preq.annotations["dp_rank"] = plan.dp_rank

        async def drive() -> None:
            get_flight_recorder().record(
                preq.request_id, "prefill_streamed",
                worker=f"{plan.worker_id:016x}", wire=plan.wire,
                est_transfer_s=round(plan.est_transfer_s, 6),
            )
            # trace hop, as in run_prefill: the clone's dispatch is its own
            # span and the prefill worker's spans parent on it
            tracer = get_tracer()
            span = tracer.span(
                "router.prefill",
                traceparent=preq.annotations.get("traceparent"),
                request_id=preq.request_id,
                worker=f"{plan.worker_id:016x}", dp_rank=plan.dp_rank,
                overlap_blocks=plan.overlap_blocks, wire=plan.wire,
                est_transfer_s=round(plan.est_transfer_s, 6), streamed=True,
            )
            with span:
                if tracer.enabled:
                    preq.annotations["traceparent"] = span.traceparent()
                try:
                    stream = await self.client.generate(
                        preq.to_obj(), context.child(), plan.worker_id
                    )
                    async for item in stream:
                        out = (
                            item if isinstance(item, BackendOutput)
                            else BackendOutput.from_obj(item)
                        )
                        if out.finish_reason is not None:
                            break
                except Exception as e:
                    # decode side recomputes whatever never streams over —
                    # the request still completes, just without the overlap
                    # win
                    span.status = "ERROR"
                    span.set(error=repr(e))
                    log.exception(
                        "streamed prefill failed for %s; decode side "
                        "recomputes", preq.request_id[:8],
                    )

        # spawn_bg: a swallowed prefill failure would silently serialize
        # every streamed request behind the decode-side wait budget
        return spawn_bg(drive())

    async def run_prefill(
        self, req: PreprocessedRequest, context: Context,
        plan: Optional[PrefillPlan] = None,
    ) -> Optional[BackendOutput]:
        """Send the max_tokens=1 clone to a prefill worker.

        Returns the prefill output (first token + kv_transfer metadata), or
        None if prefill failed/unavailable (caller falls back to aggregated).

        ``plan`` (from :meth:`plan`) pins the transfer-cost-aware worker
        choice; without one the legacy overlap-only scheduling applies.
        """
        assert self.client is not None
        preq = self._prefill_clone(req)

        # trace hop: the prefill dispatch is its own span, and the prefill
        # worker's spans parent on IT (frontend -> router.prefill -> worker)
        tracer = get_tracer()
        span = None
        if tracer.enabled:
            span = tracer.span(
                "router.prefill",
                traceparent=preq.annotations.get("traceparent"),
                request_id=preq.request_id,
            )
            span.__enter__()
            preq.annotations["traceparent"] = span.traceparent()
        instance_id: Optional[int] = None
        try:
            if plan is not None and plan.worker_id is not None:
                instance_id = plan.worker_id
                preq.annotations["dp_rank"] = plan.dp_rank
                if span is not None:
                    span.set(
                        worker=f"{instance_id:016x}",
                        dp_rank=plan.dp_rank,
                        overlap_blocks=plan.overlap_blocks,
                        wire=plan.wire,
                        est_transfer_s=round(plan.est_transfer_s, 6),
                    )
            elif self.kv_router is not None and self.client.instances:
                # dp-aware like the decode path (scheduler.rs:543-560): every
                # (instance, dp_rank) is a candidate, and the chosen rank rides
                # the annotation so the worker's DpEngineGroup dispatches to it
                cands = self._candidates()
                decision = self.kv_router.schedule_tokens(preq.token_ids, cands)
                instance_id = decision.worker.worker_id
                preq.annotations["dp_rank"] = decision.worker.dp_rank
                if span is not None:
                    span.set(
                        worker=f"{instance_id:016x}",
                        dp_rank=decision.worker.dp_rank,
                        overlap_blocks=decision.overlap_blocks,
                    )
            get_flight_recorder().record(
                preq.request_id, "prefill_routed",
                worker=(f"{instance_id:016x}" if instance_id is not None
                        else "round-robin"),
            )
            try:
                stream = await self.client.generate(preq.to_obj(), context.child(), instance_id)
                last: Optional[BackendOutput] = None
                async for item in stream:
                    out = item if isinstance(item, BackendOutput) else BackendOutput.from_obj(item)
                    last = out
                    if out.finish_reason is not None:
                        break
                if last is not None and instance_id is not None:
                    last.annotations[ANNOTATION_PREFILL_WORKER_ID] = instance_id
                return last
            except NoResponders:
                log.info("prefill pool unavailable; falling back to aggregated")
                if span is not None:
                    span.status = "ERROR"
                    span.set(error="no responders")
                return None
            except Exception as e:
                if is_terminal(e):
                    # typed 4xx-class failure (context length, guided
                    # grammar, ...): the request itself is wrong, so the
                    # aggregated path would only re-run the same doomed
                    # prefill and fail again — surface it to the client now
                    if span is not None:
                        span.status = "ERROR"
                        span.set(error=repr(e))
                    raise
                log.exception("prefill failed; falling back to aggregated")
                if span is not None:
                    span.status = "ERROR"
                    span.set(error=repr(e))
                return None
        finally:
            if span is not None:
                span.__exit__(None, None, None)

    async def stop(self) -> None:
        if self.kv_router is not None:
            await self.kv_router.stop()
        if self.client is not None:
            await self.client.stop()
