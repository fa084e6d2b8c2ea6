"""Resource-lifecycle spec table for the RESOURCE-LEAK / LOCK-ACROSS-AWAIT /
TASK-JOIN passes (lifecycle.py).

Every acquire/release-shaped resource the analyzer checks is DECLARED here,
so a new pairing (per-class token budgets, peer-tier leases, dedupe
refcounts — the ROADMAP item 1/5 resources) registers in one line instead
of a new pass. Registration workflow: add a :class:`ResourceSpec` (or
:class:`ChargeSpec` for owner-dict load charges) to the tables below, run
``python -m tools.analysis dynamo_tpu --select RESOURCE-LEAK``, fix or
baseline what it finds, and add a rule-catalog row in docs/development.md
if the semantics are novel. See docs/development.md ("How the dataflow
engine models your function") for what the engine can and cannot see.

Matching model
--------------
An *acquire* / *release* signature is ``(method_name, receiver_hints)``:
the pass matches a call whose trailing name equals ``method_name`` and
whose receiver's trailing identifier contains one of the hints (empty
hints = any receiver, including bare-name calls). The value an acquire
call returns becomes a tracked token; a token is discharged when, on a
path, it is

- passed through a *release* call (any release site for the same resource
  on the path discharges all of that resource's tokens — coarse on
  purpose),
- stored into a declared *owner* (an attribute named in ``owners``, or any
  mutation of a caller-supplied parameter — the callee's summary then
  tells callers the parameter now holds the resource),
- returned or yielded (ownership moves to the caller/consumer), or
- narrowed away (``if x is None: ...`` — a failed acquire held nothing).

Any path out of the function (including except/finally and generator-exit
edges) on which a token is still live is a RESOURCE-LEAK finding.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class ResourceSpec:
    name: str
    doc: str
    # file scope: substring match on the normalized module path
    paths: Tuple[str, ...]
    # ((method_name, (receiver_hint, ...)), ...)
    acquire: Tuple[Tuple[str, Tuple[str, ...]], ...]
    release: Tuple[Tuple[str, Tuple[str, ...]], ...]
    # attribute names that OWN the resource once stored into
    owners: Tuple[str, ...] = ()
    # functions whose body IS the acquire/release implementation — their
    # internals are exempt (they manipulate the underlying table directly)
    exempt_functions: Tuple[str, ...] = ()
    # resources whose release is structural (self-cleaning waits, process-
    # lifetime registrations): declared for the catalog, not path-checked
    self_releasing: bool = False


@dataclasses.dataclass(frozen=True)
class ChargeSpec:
    """Owner-dict load charges (the PR 13 reroute-release bug shape):
    ``self.<owner>[key] = (worker, blocks)`` books an optimistic charge
    that only :meth:`release` can undo. A subscript store into the owner
    dict may DISPLACE a live entry — the store must be preceded, in the
    same function, by a ``pop`` of the same owner (whose result feeds the
    release) or by a containment guard (``key in self.<owner>``) proving
    nothing is displaced. A bare overwrite leaks the displaced charge
    forever."""

    name: str
    doc: str
    paths: Tuple[str, ...]
    owner_attrs: Tuple[str, ...]
    release: str                      # the call that undoes one charge
    exempt_functions: Tuple[str, ...] = ()


# ---------------------------------------------------------------------------
# the table — ROADMAP items 1 and 5 add their pairs HERE
# ---------------------------------------------------------------------------

RESOURCES: Tuple[ResourceSpec, ...] = (
    ResourceSpec(
        name="kv-blocks",
        doc="KV cache pages booked from the engine allocator: every "
            "allocate/acquire_prefix must be released or appended to a "
            "sequence's block table (block_ids; under a ring, its summary "
            "blocks by window, summary_ids; a windowed page group's run, "
            "win_ids) on every path out, or the "
            "pool drains one failed dispatch at a time.",
        paths=("dynamo_tpu/engine/",),
        acquire=(
            ("allocate", ("allocator", "alloc")),
            ("acquire_prefix", ("allocator", "alloc")),
        ),
        release=(("release", ("allocator", "alloc")),),
        owners=("block_ids", "summary_ids", "win_ids"),
        exempt_functions=("allocate", "acquire_prefix", "release"),
    ),
    ResourceSpec(
        name="arena-lease",
        doc="Staging-arena slot leases (engine/transfer.py): _lease_slots "
            "grants (slots, token); an unfreed lease pins arena capacity "
            "for SLOT_LEASE_S — the PR 10 stream-exit bleed. Ownership "
            "transfers: the per-stream lease list (stream_leases) or "
            "yielding the slots to the client (its free_slots call or "
            "expiry reclaims them).",
        paths=("engine/transfer.py",),
        acquire=(("_lease_slots", ("self",)),),
        release=(("pop", ("_slot_lease",)),),
        owners=("stream_leases",),
        exempt_functions=("_lease_slots",),
    ),
    ResourceSpec(
        name="pull-reservation",
        doc="Device-offer cap reservations (_pull_pending): a uuid slot "
            "reserved for an offered device pull must be popped on failure "
            "or handed to the client (free_pull / expiry scan reclaims).",
        paths=("engine/transfer.py",),
        acquire=(),
        release=(("pop", ("_pull_pending",)),),
        owners=("_pull_pending",),
        self_releasing=True,  # expiry scan is the backstop; store-shaped acquire
    ),
    ResourceSpec(
        name="drain-lease",
        doc="The single drain slot a worker holds while it evacuates "
            "(engine/drain.py DrainLedger): acquire_drain returns a token "
            "(None when a drain is already running — the /drain 409 path); "
            "an unreleased token leaves the worker advertising 'draining' "
            "after the reclaim resolves, so no router ever sends it work "
            "again.",
        paths=("engine/drain.py",),
        acquire=(("acquire_drain", ("ledger",)),),
        release=(("release_drain", ("ledger",)),),
        exempt_functions=("acquire_drain", "release_drain"),
    ),
    ResourceSpec(
        name="checkpoint-manifest",
        doc="The checkpoint writer's manifest tmp-file handle "
            "(engine/checkpoint.py CheckpointWriter): begin_manifest hands "
            "out a tmp path that must reach commit_manifest (the atomic "
            "os.replace publish) or abort_manifest on every path out — a "
            "dangling tmp is exactly the partial-checkpoint state restores "
            "must treat as corrupt.",
        paths=("engine/checkpoint.py",),
        acquire=(("begin_manifest", ()),),
        release=(("commit_manifest", ()), ("abort_manifest", ())),
        exempt_functions=("begin_manifest", "commit_manifest",
                          "abort_manifest"),
    ),
    ResourceSpec(
        name="directory-entry",
        doc="Global KV directory advertisements (kvbm/directory.py "
            "GlobalKvDirectory): each publish stores hash->tier into "
            "_published, mirrored by a store key under kvdir/. Store-shaped "
            "acquire (publish returns a count, not a token), released by "
            "unpublish / withdraw_all / close; the store lease — or the "
            "injected-clock ts TTL on lease-less clients — is the "
            "structural backstop that ages out a dead holder's entries.",
        paths=("kvbm/directory.py",),
        acquire=(),
        release=(("unpublish", ()),),
        owners=("_published",),
        self_releasing=True,  # lease expiry / ts TTL is the backstop
    ),
    ResourceSpec(
        name="fetch-lease",
        doc="In-flight peer-tier fetch leases (GlobalKvDirectory."
            "begin_fetch): the lease MUST reach commit_fetch (blocks "
            "imported) or abort_fetch (fall back to recompute) on every "
            "path out of the fetching function — a stranded lease wedges "
            "the inflight-fetch accounting and hides a fetch that neither "
            "landed nor fell back.",
        paths=("kvbm/directory.py", "engine/engine.py", "sim/fleet.py"),
        acquire=(("begin_fetch", ()),),
        release=(("commit_fetch", ()), ("abort_fetch", ())),
        exempt_functions=("begin_fetch", "commit_fetch", "abort_fetch"),
    ),
    ResourceSpec(
        name="health-subscription",
        doc="Degradation-event subscriptions (runtime/health.py "
            "HealthMonitor.subscribe): each subscription handle keeps its "
            "callback on every future health event until close() — a "
            "dangling handle keeps publishing to a torn-down consumer "
            "(the worker __main__ closes its event-plane publisher's "
            "subscription on shutdown).",
        paths=("runtime/health.py", "engine/__main__.py", "sim/"),
        acquire=(("subscribe", ("monitor", "health")),),
        release=(("close", ("sub",)),),
        exempt_functions=("subscribe", "close"),
    ),
    ResourceSpec(
        name="kv-commit-signal",
        doc="KvCommitSignal waits are self-cleaning by construction: one "
            "shared shielded future serves every waiter and wait() never "
            "hands out a subscription handle. Declared so the pass table "
            "stays the catalog of lifecycle-shaped APIs; if the signal ever "
            "grows per-waiter registration, drop self_releasing and list "
            "the unsubscribe here.",
        paths=("engine/transfer.py",),
        acquire=(("wait", ("kv_commits", "sig")),),
        release=(),
        self_releasing=True,
    ),
)

CHARGES: Tuple[ChargeSpec, ...] = (
    ChargeSpec(
        name="router-optimistic-charge",
        doc="KvRouter's in-flight load tables (_active/_remote_active): "
            "each entry mirrors an add_local_load charge. Overwriting an "
            "entry for a re-routed request_id without releasing the "
            "superseded charge leaks phantom load onto the old worker "
            "forever — the PR 13 migration-retry bug.",
        paths=("dynamo_tpu/kv_router/",),
        owner_attrs=("_active", "_remote_active"),
        release="sub_local_load",
    ),
)


# ---------------------------------------------------------------------------
# LOCK-ACROSS-AWAIT spec
# ---------------------------------------------------------------------------

# Awaited call names that hit the request/transfer plane (or block on
# connection establishment): holding an asyncio.Lock/Semaphore across one
# of these serializes every other holder behind a peer's latency — the
# breaker-starvation shape. The call graph extends this set transitively:
# awaiting a local helper that reaches one of these also counts.
SLOW_AWAIT_NAMES = frozenset({
    "round_trip",        # request-plane client entry
    "open_connection",   # asyncio connect (OS timeout scale when peer dead)
    "create_connection",
    "getaddrinfo",
    "drain",             # stream backpressure wait
    "pull",              # KV transfer client fetch
    "_pull_stream",
})

# files where the pass applies (the async control plane; kernels and tests
# have no loop to starve)
LOCK_AWAIT_PATHS = ("dynamo_tpu/",)


# ---------------------------------------------------------------------------
# TASK-JOIN spec
# ---------------------------------------------------------------------------

# call shapes whose result is a live task/handle when stored onto self
TASK_SPAWN_NAMES = frozenset({"create_task", "ensure_future", "spawn_bg"})
# receivers whose .spawn returns a tracked handle that still wants a join
TASK_SPAWN_TRACKER_HINTS = ("tracker",)
# call names that count as joining a task
TASK_JOIN_CALL_NAMES = frozenset({"gather", "wait", "wait_for", "shield", "cancel"})
