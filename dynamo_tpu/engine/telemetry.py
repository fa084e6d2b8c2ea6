"""Engine step telemetry: a cheap per-step stats hook + Prometheus projection.

The engine loop hands a ``StepStats`` to ``engine.stats_hook`` after every
prefill chunk, every consumed decode horizon, and every fused ``mixed``
continuous-batching step (one prefill chunk riding along with a decode step
through the unified ragged kernel — its batch_occupancy shows how full the
fused launch ran; handed over when the step's results are READ, a tick after
its launch where the next mixed step was launched on its device carry first:
``mixed_chained`` says so, the wait is then the loop's ``fetch``, and the
executor's ``sync`` is left to the steps read at once). The stats are host-side
scalars read off bookkeeping the loop already maintains — the hook NEVER
touches jit-traced code or forces a device sync (durations are host wall
time around executor calls; token counts come from ``_accept_tokens``'s own
``produced`` counters).

``loop_span`` is the loop's one timing mechanism (it replaced a debug print
keyed on an environment variable). Entering it opens a
``jax.profiler.TraceAnnotation("dtpu.loop.<name>")``, so the phase lies on
the profiler's own timeline beside the device planes whenever a profile is
being taken (an inactive TraceMe costs a flag test); leaving it appends
``name, t0_ns, t1_ns`` on ``time.monotonic_ns()`` to a bounded pending
list on the engine, but only while ``engine.stats_hook`` is set. The next
``StepStats`` carries the list away as ``host_spans``, next to
``admit_wait_s`` (queued -> admitted, one value per request admitted since
the last ``StepStats``). Spans are opened on the loop thread
(``LOOP_PHASES``) and on the one step-executor thread, inside ``step``
(``EXECUTOR_PHASES``); never under ``jit``.

A span may have a SUBJECT, the id of the request whose work it is
(``REQUEST_PHASES``): ``submit``, the loop thread's synchronous work in
``TpuEngine.generate`` before a request is queued (``submit_span`` cuts it
at every ``await``: a span covers only time the thread was held), and
``deliver``, from a result leaving the request's queue to the caller asking
for the next one (``record_request_span``: it is held across a suspension
point, so it is two stamps and NO annotation, since two coroutines'
annotations would interleave on one thread's line of a profile). They run on
the event-loop thread INSIDE the loop's ``yield``, ``idle`` and the awaits of
``step`` and ``fetch``, say to whom the loop gave the thread, and ride the
next ``StepStats`` in a field of their own, ``request_spans`` (``name, t0_ns,
t1_ns, request_id``, four values a span; ``span_quads``): ``host_spans``
keeps its names, its form and its tiling of a tick.

``launch`` is the loop's one way to call a jitted program on the device
(prefill, mixed_step, decode_multi, decode, spec_multi, the draft's chunk,
reset_slot, embed, embed_chunk): the LAUNCH LEDGER. It leaves a record of the
call, ``seq, program, key, t0_ns, t1_ns, compiled, after`` (``launch_records``):
``seq`` one counter an engine in launch order; ``program`` the jitted
function's name, what a device trace shows behind ``jit_``; ``key`` what
selects the compiled executable among that program's (a chunk's bucket; the
steps a decode program advances a row); the call's two stamps; whether THIS
call compiled a program or loaded one from the compile cache (JAX recorded a
backend compile on the calling thread across it: seconds, and not the
milliseconds of a call that only meets its arguments in a form it had not
seen, a device array where a host array was); and ``after``, the ``seq`` of
the newest launch whose results the loop had taken when it made this one (-1
for none; a link launched on the device carry of the one before comes after
an OLDER launch's results).
``record_arrival`` stamps ``seq, t_ns`` on the thread that learns a launch's
results are on the host, as the blocking conversion returns: the fetch pool's,
or the executor's under ``sync``. Both ride the next ``StepStats`` as
``launches`` and ``arrivals``, flat, from bounded pending lists filled only
while ``stats_hook`` is set, like the spans: launch k, the device's k-th
execution of a program and arrival k are one to one, which is what lets a
reader put the host's and the device's clocks of a profile together
(``benchmarks/metrics/_launches.py``). Three device programs the engine's
process runs are NOT the loop's and leave no record: the vision encoder
(``encode_image``) and a disaggregated worker's ``kv_gather`` /
``kv_scatter``; a reader sees their executions as unjoined.

``host_spans`` is FLAT, three values a span, and not a tuple per span
(``span_triples`` reads it back as triples). A hook that keeps its
``StepStats`` keeps the spans, and a tuple per span is a dozen more objects
a tick for the cyclic collector to count and to walk: twice the
youngest-generation passes over a 50 s serving window on the chip (PERF.md
section 6, PR 24). Strings and integers are not the collector's business,
and one flat tuple a step is one object, which it lets go of at its first
pass.

``EngineTelemetry`` is the standard consumer: it projects StepStats onto
the runtime metrics registry (histograms split by phase, occupancy/KV/queue
gauges, spec-decode acceptance) under the caller's hierarchy labels
(``dtpu_namespace``/``dtpu_component``), and logs any step slower than
``DTPU_SLOW_STEP_MS`` (default 1000 ms — a horizon is tens of decode steps;
a multi-second step means the device stalled, the host fell behind, or a
program compiled: the line names the ``program[key]`` whose call took longest
since the step before and says ``compiled`` where it did), counts the launches
by program and key for ``/debug/worker`` (``programs``), and warns once for
each launch that compiled: a worker attaches it as it reports ready, so every
compile it sees is one a request waited for. ``bench.py`` attaches its own collector to the same hook to put
mean/p99 step time in the BENCH JSON.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.monitoring

from ..runtime import metrics as M
from ..runtime.config import ENV_SLOW_STEP_MS, env_float
from ..runtime.logging import get_logger

log = get_logger("engine.telemetry")

# from one decode step (ms) to a horizon (tens of steps); prefill chunks
# can reach tens of seconds on first compile
_STEP_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, 5.0, 15.0, 60.0)
_TOKEN_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096)

# the loop thread's phases: every instant of a loop tick lies in exactly one
LOOP_PHASES = ("idle", "admit", "book", "step", "fetch", "emit", "reap",
               "publish", "yield")
# the step-executor thread's, inside a ``step`` span of the loop thread
EXECUTOR_PHASES = ("pack", "upload", "launch", "sync")
_HOST_PHASES = LOOP_PHASES + EXECUTOR_PHASES
# spans with a subject, a request's id: on the event-loop thread, inside the
# loop's ``yield`` / ``idle`` and the awaits of ``step`` / ``fetch``
REQUEST_PHASES = ("submit", "deliver")
_ANNOTATION = {p: f"dtpu.loop.{p}" for p in _HOST_PHASES}
_ANNOTATION["submit"] = "dtpu.req.submit"  # ``deliver`` opens none
# pending spans / admission waits kept on an engine between two StepStats:
# beyond this the oldest go (a hook set on a loop that turns without stepping)
PENDING_SPANS_MAX = 4096


def pending_spans() -> collections.deque:
    """The engine's pending list: ``name, t0_ns, t1_ns`` of each span, flat."""
    return collections.deque(maxlen=3 * PENDING_SPANS_MAX)


def span_triples(host_spans: Tuple[Any, ...]):
    """``StepStats.host_spans`` (or a pending list) as ``(name, t0_ns,
    t1_ns)`` triples, oldest first."""
    return zip(host_spans[0::3], host_spans[1::3], host_spans[2::3])


def pending_request_spans() -> collections.deque:
    """The engine's pending list of spans with a subject: ``name, t0_ns,
    t1_ns, request_id`` of each, flat."""
    return collections.deque(maxlen=4 * PENDING_SPANS_MAX)


def span_quads(request_spans: Tuple[Any, ...]):
    """``StepStats.request_spans`` (or a pending list) as ``(name, t0_ns,
    t1_ns, request_id)``, in the order the spans ended."""
    return zip(request_spans[0::4], request_spans[1::4],
               request_spans[2::4], request_spans[3::4])


# the one clock of the loop's spans, a request's stamps (engine ``_Seq``),
# the launch ledger and the benchmark's marker
now_ns = time.monotonic_ns

LAUNCH_VALUES = 7  # seq, program, key, t0_ns, t1_ns, compiled, after


def pending_launches() -> collections.deque:
    """The engine's pending launch records, ``LAUNCH_VALUES`` each, flat."""
    return collections.deque(maxlen=LAUNCH_VALUES * PENDING_SPANS_MAX)


def pending_arrivals() -> collections.deque:
    """The engine's pending arrivals: ``seq, t_ns`` of each, flat."""
    return collections.deque(maxlen=2 * PENDING_SPANS_MAX)


def launch_records(launches: Tuple[Any, ...]):
    """``StepStats.launches`` (or a pending list) as ``(seq, program, key,
    t0_ns, t1_ns, compiled, after)``, in launch order."""
    return zip(*(launches[i::LAUNCH_VALUES] for i in range(LAUNCH_VALUES)))


def arrival_records(arrivals: Tuple[int, ...]):
    """``StepStats.arrivals`` (or a pending list) as ``(seq, t_ns)``, in the
    order the results landed."""
    return zip(arrivals[0::2], arrivals[1::2])


class _Compiles(threading.local):
    """Backend compiles (and loads from the compile cache: JAX records both
    under one event) that THIS thread has made."""
    n = 0


_compiles = _Compiles()


def _count_compile(event: str, _seconds: float, **_kw) -> None:
    # JAX calls its listeners on the thread that compiled
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles.n += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def launch(engine: Any, fn: Any, key: Any, *args, **kw):
    """Call the jitted program ``fn`` for the serving loop and, while a
    ``stats_hook`` is set, leave its record in the launch ledger (module
    docstring). ``fn`` is the jitted function or a wrapper that names it as
    ``fn.jitted`` (``step_program``, the multihost leader's dispatch).
    Returns ``(seq, fn's result)``: the caller keeps ``seq`` for whoever
    reads the results."""
    seq = next(engine._launch_seq)
    if engine.stats_hook is None:
        return seq, fn(*args, **kw)
    compiled = _compiles.n
    t0 = now_ns()
    out = fn(*args, **kw)
    t1 = now_ns()
    # one extend, as a span's: the list stays a whole number of records
    engine._launches.extend((
        seq, getattr(fn, "jitted", fn).__name__, key, t0, t1,
        _compiles.n > compiled, engine._read_seq,
    ))
    return seq, out


def record_arrival(engine: Any, seq: int) -> None:
    """The results of launch ``seq`` are on the host, now: called on the
    thread that learns it, as its blocking conversion returns."""
    if engine.stats_hook is not None and seq >= 0:
        engine._arrivals.extend((seq, now_ns()))


class loop_span(jax.profiler.TraceAnnotation):
    """``with loop_span(engine, "admit"): ...`` around one phase of the step
    loop (module docstring). It IS the annotation (a TraceMe starts when it
    is constructed), so a span costs one object: a dozen are made every loop
    tick. The recorded span holds the annotation's own cost: that is the
    phase's, not a hole between two phases. With a ``request_id`` the span
    has a subject and goes to the engine's ``_request_spans``."""

    __slots__ = ("_engine", "_name", "_t0", "_request_id")

    def __init__(self, engine: Any, name: str,
                 request_id: Optional[str] = None):
        self._t0 = now_ns()
        super().__init__(_ANNOTATION[name])
        self._engine = engine
        self._name = name
        self._request_id = request_id

    def __exit__(self, exc_type, exc, tb) -> bool:
        super().__exit__(exc_type, exc, tb)
        if self._request_id is not None:
            record_request_span(
                self._engine, self._name, self._t0, self._request_id
            )
        elif self._engine.stats_hook is not None:
            # one extend: the loop thread and the executor thread both come
            # here, and the list stays a whole number of triples
            self._engine._host_spans.extend(
                (self._name, self._t0, now_ns())
            )
        return False


def record_request_span(engine: Any, name: str, t0_ns: int,
                        request_id: str) -> None:
    """A span of ``REQUEST_PHASES`` that ends now: kept, like the loop's,
    only while ``engine.stats_hook`` is set."""
    if engine.stats_hook is not None:
        engine._request_spans.extend((name, t0_ns, now_ns(), request_id))


class submit_span:
    """``submit``: the loop thread's synchronous work on one request from
    the entry of ``TpuEngine.generate`` to the request being queued, as one
    ``loop_span`` for each stretch between two ``await``s::

        with submit_span(engine) as sub:
            ...; sub.request_id = req.request_id
            x = await sub.away(something())     # not the thread's time

    ``held_ms()`` is the stretches' summed length so far, the open one
    included: what the flight recorder's ``queued`` event states."""

    __slots__ = ("_engine", "request_id", "_held_ns", "_open")

    def __init__(self, engine: Any):
        self._engine = engine
        self.request_id = ""
        self._held_ns = 0
        self._open: Optional[loop_span] = None

    def __enter__(self) -> "submit_span":
        self._open = loop_span(self._engine, "submit", self.request_id)
        return self

    def close(self) -> None:
        span, self._open = self._open, None
        if span is not None:
            span._request_id = self.request_id  # known once the request is read
            span.__exit__(None, None, None)
            self._held_ns += now_ns() - span._t0

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    async def away(self, awaitable):
        self.close()
        try:
            return await awaitable
        finally:
            self.__enter__()

    def held_ms(self) -> float:
        span = self._open
        open_ns = now_ns() - span._t0 if span is not None else 0
        return (self._held_ns + open_ns) / 1e6


def _ns_by_phase(steps) -> Dict[str, int]:
    """Host nanoseconds per loop phase over the ``host_spans`` of ``steps``."""
    spent: Dict[str, int] = {}
    for s in steps:
        for name, t0, t1 in span_triples(s.host_spans):
            spent[name] = spent.get(name, 0) + (t1 - t0)
    return spent


@dataclasses.dataclass
class StepStats:
    """One engine-loop step, observed host-side."""

    phase: str                 # "prefill" | "decode" | "mixed"
    duration_s: float          # host wall time of the step's dispatch/consume
    batch_occupancy: int       # active (admitted, unfinished) slots
    batch_size: int            # configured max batch width
    tokens: int                # tokens processed: prefill chunk len / emitted
                               # ("mixed" fused steps count chunk + decode)
    queue_depth: int           # admission queue length (waiting requests)
    kv_active_blocks: int
    kv_free_blocks: int
    kv_total_blocks: int
    spec_acceptance: Optional[float] = None  # None unless spec decoding on
    # async host step-prep (engine/prep.py, DTPU_ASYNC_PREP): whether this
    # chunk-carrying step consumed a prebuilt pack (what the dispatch still
    # waited for it lies in its ``pack`` span). None on decode-only steps
    # and with async prep off.
    prep_hit: Optional[bool] = None
    # what the host did since the last StepStats: phase, t0_ns, t1_ns of
    # each span on time.monotonic_ns(), FLAT (span_triples above; module
    # docstring), loop-thread and executor-thread spans together, and
    # queued -> admitted seconds of each request admitted since then
    host_spans: Tuple[Any, ...] = ()
    admit_wait_s: Tuple[float, ...] = ()
    # ... and what it did for whom inside the loop's yield / idle / awaits:
    # name, t0_ns, t1_ns, request_id of each ``submit`` and ``deliver`` span
    # that ended since then, FLAT, four values a span (span_quads above), on
    # the same clock. NOT part of host_spans' tiling of a tick
    request_spans: Tuple[Any, ...] = ()
    # the launch ledger since then: seq, program, key, t0_ns, t1_ns,
    # compiled, after of each jitted call the loop made (launch_records
    # above), and seq, t_ns of each arrival of a launch's results
    # (arrival_records), both FLAT, on the same clock
    launches: Tuple[Any, ...] = ()
    arrivals: Tuple[int, ...] = ()
    # expert routing of the step (one-chip grouped MoE path; None elsewhere
    # and on prefill-only steps, which have no readback to carry them):
    # (token, expert) rows routed, T x K summed over layers; experts with at
    # least one row, summed over layers; the largest row count on one
    # expert, max over layers. A decode horizon sums its steps (the max
    # stays a max). Padding rows are not counted. Computed in the step
    # program from the routing it already does and read with its results.
    moe_tokens_routed: Optional[int] = None
    moe_experts_touched: Optional[int] = None
    moe_load_max: Optional[int] = None
    # where the expert layers hold one chip's share of a layer's experts
    # (MlaConfig.experts_held): the held experts with at least one row,
    # summed over layers (a horizon sums its steps); the three above then
    # count the held experts and the rows routed to them only
    moe_held_experts_touched: Optional[int] = None
    # learned sparse attention (an MlaConfig with an indexer), summed over
    # the step's real decode rows and the layers: keys a row could see, keys
    # an indexer scored (selecting layers only), keys attended over. They
    # ride the readback beside moe_*; None elsewhere
    dsa_keys_causal: Optional[int] = None
    dsa_keys_scored: Optional[int] = None
    dsa_keys_selected: Optional[int] = None
    # ... and how the selecting layers read their index keys out of the pages
    # (ops/pallas_sparse.py ``paged_index_keys``): the whole chunks of pages
    # under the step's tables, summed over selecting layers, and those of
    # them whose pages lie one after the other in the pool, read with one
    # descriptor: counted from the step's own tables
    dsa_index_chunks_whole: Optional[int] = None
    dsa_index_chunks_run: Optional[int] = None
    # a latent cache WITHOUT an indexer (an MlaConfig held as rows): the
    # keys the step's real decode rows attended over (each its whole
    # context), and those rows, both summed over layers; the same readback
    mla_keys_attended: Optional[int] = None
    mla_decode_rows: Optional[int] = None
    # ... and by the chunk: the whole chunks of pages under the contexts of
    # the step's latent rows (a mixed step's chunk row too), summed over
    # layers, and those of them whose pages lie one after the other in the
    # pool, which the kernel reads with one descriptor an array
    # (ops/pallas_latent.py): counted from the step's own tables
    mla_chunks_whole: Optional[int] = None
    mla_chunks_run: Optional[int] = None
    # decode rows the decode-only kernel serves (ops/pallas_attention.py),
    # in a decode step or horizon: the whole chunks of pages under the rows'
    # contexts, x the steps each took x the layers that launch it, and those
    # of them whose pages are consecutive block ids, which the kernel reads
    # with one descriptor an array (ops/pallas_paged.PageReader): counted on
    # the host from the ids it holds; None where no layer launches it
    paged_chunks_whole: Optional[int] = None
    paged_chunks_run: Optional[int] = None
    # a family with slot state beside its pages (a state-space mixer;
    # engine/state_cache.py), from the step's own shapes on the host: live
    # decode rows x layers whose recurrence the step advanced by one token
    # (a horizon: a row x the steps it took before its request had what it
    # asked for), a chunk's real tokens x layers it scanned,
    # and the bytes of state the slots in use hold. None elsewhere
    ssm_rows_updated: Optional[int] = None
    ssm_tokens_scanned: Optional[int] = None
    ssm_decode_steps: Optional[int] = None   # decode steps those rows took
    # the slot store's bytes in use, whatever recurrence fills it (a
    # state-space mixer's, a linear-attention layer's)
    ssm_state_bytes: Optional[int] = None
    # the same three counts for a family whose slot state is a
    # linear-attention layer's matrix state (gated delta rule: solar_open2),
    # over the layers that KEEP state (registry.state_layers): live decode
    # rows x those layers advanced one token, a chunk's real tokens x those
    # layers scanned, the decode steps the rows took. None elsewhere
    kda_rows_updated: Optional[int] = None
    kda_tokens_scanned: Optional[int] = None
    kda_decode_steps: Optional[int] = None
    # ... and for one whose matrix state decays by a fixed rate a head
    # (lightning attention: minicpm_sala)
    lightning_rows_updated: Optional[int] = None
    lightning_tokens_scanned: Optional[int] = None
    lightning_decode_steps: Optional[int] = None
    # the prefix the three counts above came under (registry.state_prefix,
    # asked by the engine): "ssm", "kda", "lightning". None without slot state
    state_prefix: Optional[str] = None
    # a family whose page layers choose BLOCKS of keys from pooled keys
    # (InfLLM-v2: models/minicpm_sala.py), on the step's own readback beside
    # moe_*: the keys the step's real decode rows' launches were handed (the
    # views' lengths, a kv head: ops/attention.infllm_decode_rows) and the
    # causal keys they could have read, the (row, layer)s whose context was past
    # dense_len (each summed over rows and sparse layers), and the pooled
    # keys every token of the step made final (a key a kv head). None elsewhere
    infllm_keys_selected: Optional[int] = None
    infllm_keys_causal: Optional[int] = None
    infllm_rows_sparse: Optional[int] = None
    infllm_pooled_keys_written: Optional[int] = None
    # a family whose pages are a ring with summaries by window (EVA:
    # models/evabyte.py), on the step's own readback beside moe_*: the real
    # decode rows attended, the exact keys they read of their open windows
    # and the summaries they read of the closed ones (all three summed over
    # rows and layers), the windows those rows closed (a row at a window's
    # first position), and the decode steps counted. None elsewhere
    eva_rows_attended: Optional[int] = None
    eva_window_keys: Optional[int] = None
    eva_summaries_read: Optional[int] = None
    eva_windows_closed: Optional[int] = None
    eva_decode_steps: Optional[int] = None
    # a family whose pages are kept BY LAYER KIND (registry.page_groups with
    # more than one group; models/cohere2_moe.py), a number a group in the
    # family's order (the group that lives as long as the request first):
    # the pages the live rows hold, on the host, and the pages its rows let
    # go behind their windows since the last StepStats (0 for a group whose
    # pages live as long as the request). None for one group
    page_groups_held: Optional[Tuple[int, ...]] = None
    page_groups_released: Optional[Tuple[int, ...]] = None
    # ... and on the step's own readback beside moe_*: the keys the step's
    # real decode rows read in sliding and in full layers, and those rows,
    # each summed over rows and the layers of its kind
    win_keys_read: Optional[int] = None
    full_keys_read: Optional[int] = None
    win_decode_rows: Optional[int] = None
    full_decode_rows: Optional[int] = None
    # a family whose SLIDING layers hold a latent of their own under a window
    # (models/dots3_note.py; its full layers count under dsa_* above), on the
    # same readback: the keys the step's real decode rows read inside their
    # windows, those rows, and the real tokens of a mixed step's chunk, each
    # summed over the sliding layers
    winlat_keys_read: Optional[int] = None
    winlat_rows: Optional[int] = None
    winlat_chunk_tokens: Optional[int] = None
    # a family that runs its stack several times a token and keeps a cache
    # slot a (pass, layer) (models/ouro.py), on the same readback (decode and
    # mixed steps): the real tokens that entered the stack (a chunk's and the
    # decode rows), the same tokens counted once a pass they went through,
    # and the keys the decode rows' attention read, summed over rows and
    # slots. None elsewhere
    ouro_stack_tokens: Optional[int] = None
    ouro_pass_tokens: Optional[int] = None
    ouro_slot_keys_read: Optional[int] = None
    # host-to-device placements the dispatches made since the last StepStats
    # (engine _upload / _dev): host values handed to a jitted call, one
    # transfer each, and per-slot arrays placed again because they changed.
    # A steady synchronous step makes one (its packed buffer); the prep
    # thread's three a chunk, made under the previous step, are not counted
    h2d_placements: int = 0
    # a ``mixed`` step only (None on ``prefill`` and ``decode``): whether it
    # was launched while the mixed step before it had not been read, on that
    # one's device carry (engine _loop: a mixed step is a link of the decode
    # chain). False where the loop had to read first: the first mixed step
    # after a horizon, a guided row, no room to book past the token in flight
    mixed_chained: Optional[bool] = None


def moe_load_imbalance(s: StepStats) -> Optional[float]:
    """Largest expert load over the mean load of the experts that got rows
    (1.0 = even), from the three counters alone: a horizon's sums are over
    the same (expert, layer, step) cells, so their ratio is the mean."""
    if not s.moe_tokens_routed or not s.moe_experts_touched:
        return None
    return s.moe_load_max * s.moe_experts_touched / s.moe_tokens_routed


def run_chunk_share(steps, reader: str = "mla") -> Optional[float]:
    """Of the whole chunks the steps' latent rows read (``reader`` "mla"),
    their indexers' keys were read by ("dsa_index") or their decode rows
    read through the decode-only kernel ("paged"), the share read as runs of
    consecutive pages; None where none was read."""
    whole = sum(getattr(s, f"{reader}_chunks_whole") or 0 for s in steps)
    run = sum(getattr(s, f"{reader}_chunks_run") or 0 for s in steps)
    return run / whole if whole else None


class EngineTelemetry:
    """StepStats -> Prometheus + slow-step log. Construct one per engine
    with a scope already stamped with the component hierarchy (and a
    ``dp_rank`` label for dp groups); ranks share the underlying metric
    objects through the scope cache."""

    def __init__(self, scope: M.MetricsScope,
                 slow_step_s: Optional[float] = None):
        self.slow_step_s = (
            env_float(ENV_SLOW_STEP_MS, 1000.0) / 1e3
            if slow_step_s is None else slow_step_s
        )
        self.steps = 0
        self._dur = scope.histogram(
            M.STEP_DURATION_SECONDS,
            "engine step duration (host-observed), split by phase",
            extra_labels=("phase",), buckets=_STEP_BUCKETS,
        )
        self._tokens = scope.histogram(
            M.STEP_TOKENS, "tokens processed per engine step",
            extra_labels=("phase",), buckets=_TOKEN_BUCKETS,
        )
        self._occupancy = scope.gauge(
            M.BATCH_OCCUPANCY, "active sequences in the decode batch"
        )
        self._queue = scope.gauge(
            M.QUEUED_REQUESTS, "requests waiting in the engine admission queue"
        )
        self._kv_active = scope.gauge(
            M.KV_ACTIVE_BLOCKS, "KV blocks pinned by active sequences"
        )
        self._kv_free = scope.gauge(M.KV_FREE_BLOCKS, "free KV blocks")
        self._kv_total = scope.gauge(M.KV_TOTAL_BLOCKS, "configured KV blocks")
        self._spec = scope.gauge(
            M.SPEC_ACCEPTANCE,
            "speculative decoding acceptance rate (emitted / drafted)",
        )
        self._slow = scope.counter(
            M.SLOW_STEPS_TOTAL, "steps slower than DTPU_SLOW_STEP_MS",
            extra_labels=("phase",),
        )
        # host seconds per loop phase (loop_span): the rate of each series is
        # that phase's share of the loop's wall time. One counter family,
        # no histogram per phase.
        self._loop_phase = scope.counter(
            M.LOOP_PHASE_SECONDS_TOTAL,
            "host seconds spent in each phase of the engine step loop",
            extra_labels=("phase",),
        )
        self._moe_imbalance = scope.gauge(
            M.MOE_LOAD_IMBALANCE,
            "largest expert load over the mean load of touched experts, last step",
        )
        self._ssm_bytes = scope.gauge(
            M.SSM_STATE_BYTES,
            "bytes of slot state (a state-space mixer's, a linear-attention "
            "layer's) held by the slots in use",
        )
        self.slow_steps = 0
        # small rolling window + last-seen gauges for the /debug/worker
        # snapshot (runtime/health.py): step telemetry without a Prometheus
        # scrape-and-parse round trip
        self._recent: "collections.deque[StepStats]" = collections.deque(
            maxlen=128
        )
        self._last: Optional[StepStats] = None
        # the launch ledger folded: program -> key -> [launches, compiled]
        self._programs: Dict[str, Dict[Any, list]] = {}

    def snapshot(self) -> Dict[str, Any]:
        """The step-telemetry section of the worker's ``/debug/worker``
        document: rolling per-phase step-time means plus the last step's
        occupancy/queue/KV view."""
        recent = list(self._recent)
        by_phase: Dict[str, Dict[str, Any]] = {}
        for s in recent:
            agg = by_phase.setdefault(
                s.phase, {"steps": 0, "duration_sum_s": 0.0, "tokens": 0}
            )
            agg["steps"] += 1
            agg["duration_sum_s"] += s.duration_s
            agg["tokens"] += s.tokens
        phases = {
            phase: {
                "steps": agg["steps"],
                "mean_step_s": round(agg["duration_sum_s"] / agg["steps"], 6),
                "tokens": agg["tokens"],
            }
            for phase, agg in sorted(by_phase.items())
        }
        loop_ns = _ns_by_phase(recent)
        out: Dict[str, Any] = {
            "steps_total": self.steps,
            "slow_steps_total": self.slow_steps,
            "recent": phases,
            # mean host seconds per loop phase per step over the window
            "loop_phases": {
                name: round(loop_ns[name] / 1e9 / len(recent), 6)
                for name in _HOST_PHASES if name in loop_ns
            },
            # every jitted call the loop made since this telemetry was
            # attached, by program and by what selects its executable
            "programs": {
                program: {
                    str(key): {"launches": n, "compiled": c}
                    for key, (n, c) in by_key.items()
                }
                for program, by_key in self._programs.items()
            },
        }
        if recent:
            # mean host-to-device placements a step over the window
            out["h2d_placements"] = round(
                sum(s.h2d_placements for s in recent) / len(recent), 3
            )
            # of the window's mixed steps, the share launched before the
            # one before them was read (absent where it held none)
            mixed = [s.mixed_chained for s in recent if s.mixed_chained is not None]
            if mixed:
                out["mixed_chained"] = round(sum(mixed) / len(mixed), 3)
        last = self._last
        if last is not None:
            out["last"] = {
                "phase": last.phase,
                "batch_occupancy": last.batch_occupancy,
                "batch_size": last.batch_size,
                "queue_depth": last.queue_depth,
                "kv_active_blocks": last.kv_active_blocks,
                "kv_free_blocks": last.kv_free_blocks,
                "kv_total_blocks": last.kv_total_blocks,
            }
        # the last step that carried expert routing counters (one-chip MoE)
        moe = next(
            (s for s in reversed(recent) if s.moe_tokens_routed is not None),
            None,
        )
        if moe is not None:
            out["moe"] = {
                "phase": moe.phase,
                "tokens_routed": moe.moe_tokens_routed,
                "experts_touched": moe.moe_experts_touched,
                "load_max": moe.moe_load_max,
            }
            if moe.moe_held_experts_touched is not None:
                out["moe"]["held_experts_touched"] = moe.moe_held_experts_touched
            if moe.dsa_keys_causal is not None:
                # the last such step's selection: what the indexer kept
                out["dsa"] = {
                    "phase": moe.phase,
                    "keys_causal": moe.dsa_keys_causal,
                    "keys_scored": moe.dsa_keys_scored,
                    "keys_selected": moe.dsa_keys_selected,
                    "index_run_chunk_share": run_chunk_share(
                        recent, "dsa_index"),
                }
            if moe.mla_keys_attended is not None:
                # the last such step's dense latent reads
                out["mla"] = {
                    "phase": moe.phase,
                    "keys_attended": moe.mla_keys_attended,
                    "decode_rows": moe.mla_decode_rows,
                    "run_chunk_share": run_chunk_share(recent),
                }
        if any(s.paged_chunks_whole is not None for s in recent):
            # the decode-only kernel's reads by the chunk, over the window
            out["paged"] = {
                "chunks_whole": sum(s.paged_chunks_whole or 0 for s in recent),
                "chunks_run": sum(s.paged_chunks_run or 0 for s in recent),
                "run_chunk_share": run_chunk_share(recent, "paged"),
            }
        if last is not None and last.ssm_state_bytes is not None:
            # the second kind of state: what the window's steps advanced
            # (under the family's prefix, registry.state_prefix: a
            # state-space mixer "ssm", a linear-attention layer "kda" or
            # "lightning")
            pre = last.state_prefix or "ssm"
            out[pre] = {
                "state_bytes": last.ssm_state_bytes,
                "rows_updated": sum(
                    getattr(s, f"{pre}_rows_updated") or 0 for s in recent),
                "tokens_scanned": sum(
                    getattr(s, f"{pre}_tokens_scanned") or 0 for s in recent),
            }
        if any(s.infllm_keys_causal for s in recent):
            # block-sparse attention over pooled keys: what the window's
            # decode rows chose of what they could have read
            out["infllm"] = {
                name: sum(getattr(s, f"infllm_{name}") or 0 for s in recent)
                for name in ("keys_selected", "keys_causal", "rows_sparse",
                             "pooled_keys_written")
            }
        if any(s.eva_rows_attended for s in recent):
            # the third kind of state: what the window's decode rows read of
            # their rings and of their closed windows' summaries
            out["eva"] = {
                name: sum(getattr(s, f"eva_{name}") or 0 for s in recent)
                for name in ("rows_attended", "window_keys", "summaries_read",
                             "windows_closed", "decode_steps")
            }
        if any(s.ouro_stack_tokens for s in recent):
            # a stack run several times a token: what went through its
            # passes, and what the decode rows read of its slots
            out["ouro"] = {
                name: sum(getattr(s, f"ouro_{name}") or 0 for s in recent)
                for name in ("stack_tokens", "pass_tokens", "slot_keys_read")
            }
        if last is not None and last.page_groups_held is not None:
            # pages by layer kind: what the live rows hold a group, and what
            # the window's steps let go behind their windows
            out["page_groups"] = {
                "held": list(last.page_groups_held),
                "released": [
                    sum(col) for col in zip(*(
                        s.page_groups_released for s in recent
                        if s.page_groups_released is not None
                    ))
                ],
                "win_keys_read": sum(s.win_keys_read or 0 for s in recent),
                "full_keys_read": sum(s.full_keys_read or 0 for s in recent),
            }
            if any(s.winlat_rows is not None for s in recent):
                # a windowed latent: what the window's decode rows read of it
                out["page_groups"].update({
                    name: sum(getattr(s, name) or 0 for s in recent)
                    for name in ("winlat_keys_read", "winlat_rows",
                                 "winlat_chunk_tokens")
                })
        return out

    def on_step(self, s: StepStats) -> None:
        try:
            self.steps += 1
            self._recent.append(s)
            self._last = s
            self._dur.observe(s.duration_s, phase=s.phase)
            if s.tokens > 0:
                self._tokens.observe(s.tokens, phase=s.phase)
            self._occupancy.set(s.batch_occupancy)
            self._queue.set(s.queue_depth)
            self._kv_active.set(s.kv_active_blocks)
            self._kv_free.set(s.kv_free_blocks)
            self._kv_total.set(s.kv_total_blocks)
            if s.spec_acceptance is not None:
                self._spec.set(s.spec_acceptance)
            if s.ssm_state_bytes is not None:
                self._ssm_bytes.set(s.ssm_state_bytes)
            imbalance = moe_load_imbalance(s)
            if imbalance is not None:
                self._moe_imbalance.set(imbalance)
            spent = _ns_by_phase((s,))
            for name in _HOST_PHASES:  # the label's fixed set
                if name in spent:
                    self._loop_phase.inc(spent[name] / 1e9, phase=name)
            calls = list(launch_records(s.launches))
            for _, program, key, t0, t1, compiled, _ in calls:
                count = self._programs.setdefault(program, {}).setdefault(
                    key, [0, 0])
                count[0] += 1
                count[1] += compiled
                if compiled:
                    log.warning(
                        "program compiled while serving: %s[%s], %.1f s",
                        program, key, (t1 - t0) / 1e9,
                    )
            if s.duration_s > self.slow_step_s:
                self.slow_steps += 1
                self._slow.inc(phase=s.phase)
                # the phase that held most of the host's time since the last
                # StepStats; the executor's phases lie inside ``step``
                if "step" in spent:
                    spent["step"] -= sum(
                        spent.get(p, 0) for p in EXECUTOR_PHASES
                    )
                longest = max(spent, key=spent.get, default="no span")
                launched = ""
                if calls:  # the longest call made since the step before
                    _, program, key, t0, t1, compiled, _ = max(
                        calls, key=lambda rec: rec[4] - rec[3])
                    launched = "; launched %s[%s] in %.0f ms%s" % (
                        program, key, (t1 - t0) / 1e6,
                        ", compiled" if compiled else "",
                    )
                log.warning(
                    "slow %s step: %.0f ms of which %s %.0f ms%s (threshold "
                    "%.0f ms; occupancy %d/%d, queue %d, kv %d/%d blocks)",
                    s.phase, s.duration_s * 1e3,
                    longest, spent.get(longest, 0) / 1e6, launched,
                    self.slow_step_s * 1e3,
                    s.batch_occupancy, s.batch_size, s.queue_depth,
                    s.kv_active_blocks, s.kv_total_blocks,
                )
        except Exception:
            # telemetry must never take the step loop down
            log.exception("step telemetry projection failed")
