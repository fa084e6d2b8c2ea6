"""Host-side physical KV block allocator with content-addressed prefix cache.

The device cache is ``[num_blocks, block_size, kv_heads, head_dim]`` per layer
(ops/attention.py layout); this allocator owns which physical block holds
which sequence-hash, mirrored after the reference's block pool + reuse logic
(lib/llm/src/block_manager/pool/) at G1 scope. Block 0 is reserved as scratch
for padding writes and never allocated.

Emits stored/removed events (sequence-hash space) for the KV router feed.

A family whose pages live one window (``models/registry.window_ring``) holds
them as a RING (``Ring`` below): the table's entry of a position wraps, a
request never holds more than a window's pages, and a closed window's pages
are written again by the next one: nothing is released and taken again, nothing
is copied. What such a request keeps of a closed window is a summary block,
from a second ``BlockAllocator`` over a store of its own blocks.

A family whose pages are kept BY LAYER KIND (``models/registry.page_groups``)
has a ``BlockAllocator`` a group, content-addressed by the same sequence
hashes. A windowed group's requests let their pages go as they fall behind the
window (``release(..., behind=True)``: a reference dropped, nothing
overwritten), and its allocator keeps what a prefix hit has used before
longest (``keep_hits``): the order in which such a group gives pages up is
written at ``_pop_free``.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Tuple

from ..tokens import SequenceHash


class OutOfBlocks(Exception):
    pass


@dataclasses.dataclass(frozen=True)
class Ring:
    """The geometry of pages with a lifetime shorter than their request:
    ``positions`` a window (the ring), ``page`` tokens a page (a page is a
    chunk: one summary each), ``max_context`` the longest sequence. A row's
    table is ``pages`` ring entries, then one summary block a window
    (``windows`` of them; a block is ``pages_per_block`` whole pages of
    summaries, ops/attention.py has the layout)."""

    positions: int
    page: int
    max_context: int

    def __post_init__(self):
        if self.positions % (self.page * self.page):
            raise ValueError(
                f"a window of {self.positions} positions is not a whole "
                f"number of pages of summaries ({self.page} summaries of "
                f"{self.page} positions each)"
            )

    @property
    def pages(self) -> int:
        return self.positions // self.page

    @property
    def windows(self) -> int:
        return -(-self.max_context // self.positions)

    @property
    def pages_per_block(self) -> int:
        return self.pages // self.page

    @property
    def table_width(self) -> int:
        return self.pages + self.windows

    def entry(self, position: int) -> int:
        """The table entry whose page holds ``position``."""
        return (position % self.positions) // self.page

    def held(self, length: int) -> Tuple[int, int]:
        """(pages, summary blocks) a sequence of ``length`` tokens holds: its
        pages up to a whole ring, one block a window it has opened."""
        return (
            min(-(-length // self.page), self.pages),
            -(-length // self.positions),
        )


@dataclasses.dataclass
class WindowGroup:
    """A group of page layers whose pages live one WINDOW of positions
    (``models/registry.page_groups``): its pool's allocator and where a row
    of the block table keeps it. A request holds the pages of the positions
    a later query can still read, ``[first_needed(q), ...)``, as a run
    ``first .. first + n`` of page indexes; the row's table holds that run
    at columns ``col .. col + pages`` and ``first`` itself at ``col +
    pages``: to an attention launch the row is the paged sequence that
    starts at position ``first * page`` (ops/paged_attention.GroupView)."""

    layers: Tuple[int, ...]
    window: int
    page: int
    pages: int                 # entries of the run in a row's table
    col: int                   # the run's first column of a row's table
    allocator: "BlockAllocator"
    released: int = 0          # pages let go behind a window (StepStats)

    def first_needed(self, q: int) -> int:
        """The first page a query at position ``q`` (or later) reads: its
        window is the ``window`` keys up to its own."""
        return max(0, q - self.window + 1) // self.page


class BlockAllocator:
    SCRATCH = 0

    def __init__(self, num_blocks: int, block_size: int,
                 keep_hits: bool = False):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is scratch)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))  # pop() -> low ids first
        # committed content: seq_hash -> block id (active or cached)
        self._by_hash: Dict[SequenceHash, int] = {}
        self._refcount: Dict[int, int] = {}            # block id -> active refs
        self._hash_of: Dict[int, SequenceHash] = {}    # block id -> seq_hash
        # LRU of unpinned cached blocks (block ids), eviction order = insertion
        self._lru: OrderedDict[int, None] = OrderedDict()
        # a windowed group's (``keep_hits``): the cached blocks a prefix hit
        # has pinned before, given up only after every other (``_pop_free``)
        self._keep_hits = keep_hits
        self._hit: set = set()
        self._lru_hit: OrderedDict[int, None] = OrderedDict()
        self.events_stored: List[List[SequenceHash]] = []
        self.events_removed: List[List[SequenceHash]] = []

    # -- introspection -------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free) + len(self._lru) + len(self._lru_hit)

    @property
    def active_blocks(self) -> int:
        return sum(1 for rc in self._refcount.values() if rc > 0)

    @property
    def cached_blocks(self) -> int:
        return len(self._lru) + len(self._lru_hit)

    # -- prefix cache --------------------------------------------------------
    def match_prefix(self, hashes: List[SequenceHash]) -> List[int]:
        """Longest cached prefix; returns (unpinned) block ids, no state change."""
        out: List[int] = []
        for h in hashes:
            bid = self._by_hash.get(h)
            if bid is None:
                break
            out.append(bid)
        return out

    def acquire_prefix(self, hashes: List[SequenceHash]) -> List[int]:
        """Pin the longest cached prefix for a request; returns its block ids."""
        return self.acquire(self.match_prefix(hashes))

    def acquire(self, ids: List[int]) -> List[int]:
        """Pin cached blocks a prefix hit found (``match_prefix``, or a
        windowed group's run of them); under ``keep_hits`` they are the ones
        given up last from now on."""
        for bid in ids:
            self._pin(bid)
        if self._keep_hits:
            self._hit.update(ids)
        return ids

    def lookup(self, seq_hash: SequenceHash):
        """The block that holds ``seq_hash``, or None; no state change."""
        return self._by_hash.get(seq_hash)

    def _pin(self, bid: int) -> None:
        rc = self._refcount.get(bid, 0)
        if rc == 0:
            self._lru.pop(bid, None)
            self._lru_hit.pop(bid, None)
        self._refcount[bid] = rc + 1

    # -- allocation ----------------------------------------------------------
    def allocate(self, n: int) -> List[int]:
        """Grab n fresh blocks (evicting cached LRU if needed); pinned, no
        content hash yet (assign via commit)."""
        out: List[int] = []
        try:
            for _ in range(n):
                out.append(self._pop_free())
        except OutOfBlocks:
            for bid in out:  # roll back partial allocation
                self._free.append(bid)
            raise
        for bid in out:
            self._refcount[bid] = 1
        return out

    def _pop_free(self) -> int:
        """A block to write: a free one, else the cached block given up
        first. The order: blocks released longest ago first (a windowed
        group's pages that fell BEHIND their request's window before those
        a request released at its end: ``release``), and under ``keep_hits``
        every block no prefix hit has used before any that one has: the tail
        of a document a session asks about again and again outlives the
        questions and answers of the turns before."""
        if self._free:
            return self._free.pop()
        lru = self._lru or self._lru_hit
        if lru:
            victim, _ = lru.popitem(last=False)  # evict oldest
            self._hit.discard(victim)
            h = self._hash_of.pop(victim, None)
            if h is not None:
                del self._by_hash[h]
                self.events_removed.append([h])
            self._refcount.pop(victim, None)
            return victim
        raise OutOfBlocks(f"no free blocks ({self.num_blocks} total)")

    def can_allocate(self, n: int) -> bool:
        return self.free_blocks >= n

    # -- content commit / release -------------------------------------------
    def commit(self, bid: int, seq_hash: SequenceHash) -> None:
        """Blocks become content-addressed once sealed (full of tokens)."""
        existing = self._by_hash.get(seq_hash)
        if existing is not None and existing != bid:
            # duplicate content: keep both physical blocks but hash points at
            # the original; this block stays anonymous (freed on release)
            return
        self._by_hash[seq_hash] = bid
        self._hash_of[bid] = seq_hash
        self.events_stored.append([seq_hash])

    def release(self, block_ids: List[int], behind: bool = False) -> None:
        """Unpin a request's blocks; sealed ones become evictable cache,
        anonymous ones return to the free list. ``behind``: a windowed
        group's pages that fell behind their live request's window; of
        those, one that no prefix hit has used is the first to be given up
        (a hit would need the whole window that ends at it)."""
        for bid in block_ids:
            rc = self._refcount.get(bid, 0)
            if rc > 1:
                self._refcount[bid] = rc - 1
                continue
            self._refcount.pop(bid, None)
            if bid not in self._hash_of:
                self._free.append(bid)
            elif bid in self._hit:
                self._lru_hit[bid] = None
                self._lru_hit.move_to_end(bid)
            else:
                self._lru[bid] = None
                self._lru.move_to_end(bid, last=not behind)

    def drain_events(self) -> Tuple[List[List[SequenceHash]], List[List[SequenceHash]]]:
        stored, self.events_stored = self.events_stored, []
        removed, self.events_removed = self.events_removed, []
        return stored, removed

    def clear(self) -> None:
        """Drop the whole prefix cache (router gets a CLEARED event upstream)."""
        for bid in [*self._lru, *self._lru_hit]:
            h = self._hash_of.pop(bid, None)
            if h is not None:
                self._by_hash.pop(h, None)
            self._free.append(bid)
        self._lru.clear()
        self._lru_hit.clear()
        self._hit.clear()
