"""Async host step-prep: build step N+1's packed chunk arrays under step N.

Host-side step preparation — bucket-padding the next prefill chunk's
token/position/block-id arrays and pushing them to the device — is pure
Python that used to run serially inside the dispatch executor, directly
bounding tok/s (the device bench is dead on this image, so host overhead
IS the measured number). ``ChunkPrep`` moves that work onto a dedicated
prep thread: the moment step N's device call is dispatched (device compute
is asynchronous from that point), the NEXT chunk's arrays are built — and
uploaded — while the device is still busy with step N.

Byte-identity with serial prep is structural, not best-effort:

- ``_build`` runs the engine's own ``_chunk_arrays`` (the one packing
  routine behind prefill, mixed and embed chunks) on an immutable
  snapshot — the prompt token ids and the chunk span's prompt-region block
  ids are both fixed at admission;
- ``take()`` hands a prebuilt result over ONLY when the serial path's key
  (request id, chunk start, chunk length, the exact block-id list) matches
  the snapshot the build used. Any divergence — cancellation, a
  migration/disagg resume moving ``prefill_pos``, block-table surgery —
  misses silently and the caller packs serially.

Block booking (``_book_decode_blocks``) deliberately stays on the event-
loop thread: the allocator is loop-owned (admission, commit and reap all
mutate it there), and the loop thread is already concurrent with in-flight
device compute — moving booking to another thread would buy races, not
overlap.

``DTPU_ASYNC_PREP`` (default on) gates the pipeline; ``StepStats`` carries
``prep_hit``, whether the step consumed a prebuild. Nothing here takes a
time of its own: what a dispatch still waits for a build lies in its
``pack`` span (engine/telemetry.py ``loop_span``, the engine's one timing
mechanism), and a miss shows as three more ``h2d_placements``.
Multihost engines keep serial prep (dispatch args there are part of the
leader's replay-ordered broadcast).
"""

from __future__ import annotations

import os
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, Optional, Tuple

from ..runtime.config import ENV_ASYNC_PREP


def async_prep_enabled() -> bool:
    return os.environ.get(ENV_ASYNC_PREP, "1").lower() not in (
        "0", "", "false", "off"
    )


class ChunkPrep:
    """One per engine. ``schedule()`` is called from the dispatch executor
    right after a chunk's device call is in flight; ``take()`` is called by
    the next chunk's dispatch. Keys are exact-match, so a stale or wrong
    prebuild can never change what the device sees."""

    def __init__(
        self,
        chunk_arrays: Callable,          # engine._chunk_arrays (pure)
        upload: Optional[Callable] = None,  # jnp.asarray; None = host-only
    ):
        self._chunk_arrays = chunk_arrays
        self._upload = upload
        self._ex = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpu-prep"
        )
        # request_id -> (key, Future[(arrays, uploads)])
        self._pending: Dict[str, Tuple[tuple, Future]] = {}
        # whether the most recent take() found a chunk prebuilt (None: none
        # was scheduled), consumed by engine._step_stats
        self.last: Optional[bool] = None

    @staticmethod
    def _key(rid: str, token_ids, start: int, chunk_len: int,
             block_ids) -> tuple:
        # content-exact over precisely what _chunk_arrays reads: the
        # chunk's token SLICE (so a reused request id with an edited
        # prompt can never key-match a stale prebuild) plus the block-id
        # list. O(chunk) — same order as the packing it guards.
        return (
            rid, int(start), int(chunk_len),
            tuple(token_ids[start : start + chunk_len]),
            tuple(block_ids),
        )

    def _build(self, token_ids, start: int, chunk_len: int, block_ids):
        arrays = self._chunk_arrays(token_ids, start, chunk_len, block_ids)
        uploads = (
            tuple(self._upload(a) for a in arrays)
            if self._upload is not None else None
        )
        return arrays, uploads

    def schedule(self, rid: str, token_ids, start: int, chunk_len: int,
                 block_ids) -> None:
        """Prebuild (and pre-upload) one chunk. ``token_ids`` must be a
        list the caller will not mutate (the engine passes the fresh list
        ``Sequence.tokens()`` builds per call — no copy needed here, and a
        full-prompt copy per chunk would be O(prompt^2) per request);
        ``block_ids`` IS snapshotted (the engine mutates that list)."""
        if len(self._pending) > 64:
            # stale entries (cancelled/reaped requests) are bounded, not
            # tracked: correctness never depends on the cache's contents
            self._pending.clear()
        blocks = list(block_ids)
        key = self._key(rid, token_ids, start, chunk_len, blocks)
        self._pending[rid] = (
            key,
            self._ex.submit(self._build, token_ids, start, chunk_len, blocks),
        )

    def take(self, rid: str, token_ids, start: int, chunk_len: int,
             block_ids):
        """The prebuilt (arrays, uploads) for an exactly-matching chunk, or
        None (caller packs serially). Waits for an in-flight build — even a
        partial overlap beats rebuilding from scratch."""
        ent = self._pending.pop(rid, None)
        if ent is None:
            self.last = None
            return None
        key, fut = ent
        self.last = False
        if key != self._key(rid, token_ids, start, chunk_len, block_ids):
            return None
        try:
            built = fut.result()
        except Exception:
            # a prep failure must never take the dispatch down; the serial
            # path recomputes (and surfaces any real packing error)
            return None
        self.last = True
        return built

    def pop_last(self) -> Optional[bool]:
        last, self.last = self.last, None
        return last

    def stop(self) -> None:
        self._ex.shutdown(wait=False)
