"""Block-level view of the neighbor edge-list array + cache models.

Converts a sampler access trace (node IDs in request order) into the block
request stream a 4 KB-granular device serves, and provides the two cache
models the paper contrasts:

* ``LRUCache`` — the OS page cache (opportunistic, recency-based), used by
  the mmap engine.
* ``PinnedCache`` — the direct-I/O user-space scratchpad: the runtime
  *manually* pins the hottest blocks (hot = high-degree nodes, which
  dominate the neighbor-sampling request stream in power-law graphs) and
  never pays kernel-stack costs.  "Optimized for latency first, locality
  second" (§IV-C).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.graph import CSRGraph

EDGE_ENTRY_BYTES = 8    # the paper's 8-byte neighbor entries (§III-B)

#: The payload of a live-cache slot that ``LRUCache.lookup_run`` claimed
#: for a block still being read: a miss to every ``lookup_run`` until
#: ``fill``.  (Stores that read through ``get``/``put`` never reserve.)
RESERVED = object()


@dataclasses.dataclass
class BlockTrace:
    """Per-request block extents for one batch's touched nodes."""
    first_block: np.ndarray      # (R,) int64
    n_blocks: np.ndarray         # (R,) int64 blocks per request
    total_blocks: int            # sum(n_blocks) — block fetches if uncached
    unique_blocks: int
    chunk_bytes: np.ndarray      # (R,) exact neighbor-list bytes per request

    @property
    def n_requests(self) -> int:
        return int(self.first_block.shape[0])

    def raw_block_bytes(self, block_bytes: int) -> int:
        """Bytes moved when every request fetches whole blocks (Fig. 10a)."""
        return int(self.total_blocks) * block_bytes


def block_trace(g: CSRGraph, touched_nodes: np.ndarray,
                block_bytes: int = 4096) -> BlockTrace:
    t = np.asarray(touched_nodes, np.int64)
    start = g.indptr[t] * EDGE_ENTRY_BYTES
    end = g.indptr[t + 1] * EDGE_ENTRY_BYTES
    first = start // block_bytes
    # degree-0 nodes still cost one metadata block probe
    last = np.maximum(end - 1, start) // block_bytes
    n_blocks = last - first + 1
    # unique blocks across the whole batch
    uniq = set()
    for f, n in zip(first, n_blocks):
        uniq.update(range(int(f), int(f + n)))
    return BlockTrace(first_block=first, n_blocks=n_blocks,
                      total_blocks=int(n_blocks.sum()),
                      unique_blocks=len(uniq),
                      chunk_bytes=np.maximum(end - start, 1))


class LRUCache:
    """O(1) LRU over block IDs (the OS page cache model).

    Doubles as a *live* page cache: ``get``/``put`` carry block payloads
    (the bytes a paged reader fetched from disk), so the same recency
    policy that the trace-replay engines model also serves real reads in
    ``storage.store.DiskStore``.  Hit/miss/eviction counters cover both
    uses.
    """

    def __init__(self, capacity_blocks: int):
        from collections import OrderedDict
        self.capacity = max(1, int(capacity_blocks))
        self._od = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def access(self, block: int) -> bool:
        """Touch a block (payload-less, trace-replay use); True on hit."""
        od = self._od
        if block in od:
            od.move_to_end(block)
            self.hits += 1
            return True
        self.misses += 1
        od[block] = None
        if len(od) > self.capacity:
            od.popitem(last=False)
            self.evictions += 1
        return False

    def access_run(self, first: int, n: int) -> int:
        """Touch blocks [first, first+n); returns number of misses."""
        return sum(0 if self.access(first + i) else 1 for i in range(n))

    # -- live-cache path (payload-carrying) ---------------------------------
    def get(self, block: int):
        """Payload for ``block`` or None on miss (counts either way)."""
        od = self._od
        if block in od:
            od.move_to_end(block)
            self.hits += 1
            return od[block]
        self.misses += 1
        return None

    def peek(self, block: int):
        """Payload if resident (touches recency, no counters) — the
        post-fetch re-check of the sharded read path, where the fetch
        itself already counted."""
        od = self._od
        if block in od:
            od.move_to_end(block)
            return od[block]
        return None

    def put(self, block: int, payload) -> tuple[int, object] | None:
        """Insert a fetched block's payload, evicting the LRU block.

        Returns the evicted ``(block, payload)`` pair, or None if nothing
        was displaced — callers that recycle backing slots (the device
        feature cache) reuse the victim's payload as the new resident's
        slot."""
        od = self._od
        od[block] = payload
        od.move_to_end(block)
        if len(od) > self.capacity:
            evicted = od.popitem(last=False)
            self.evictions += 1
            return evicted
        return None

    # -- batched live-cache path (``DiskStore``'s block-batched reads) -------
    def lookup_run(self, blocks) -> tuple[list, list, list, int]:
        """Touch distinct ``blocks`` in order, as ``access`` would one at a
        time, for a reader that fetches its misses afterwards: a resident
        block is a hit; a missing one counts as a miss and takes its slot
        at once, ``RESERVED`` (evicting the LRU block as ``put`` would)
        until ``fill`` stores its payload.  A slot another reader reserved
        and has not filled yet is a miss as well (touched, not
        re-inserted).  Returns the hits' positions in ``blocks`` and
        payloads, the misses' positions, and the number of evictions."""
        od = self._od
        cap = self.capacity
        hit_at, found, miss_at = [], [], []
        evictions = 0
        for k, b in enumerate(blocks):
            data = od.get(b)
            if data is None:
                miss_at.append(k)
                od[b] = RESERVED
                if len(od) > cap:
                    od.popitem(last=False)
                    evictions += 1
            else:
                od.move_to_end(b)
                if data is RESERVED:
                    miss_at.append(k)
                else:
                    hit_at.append(k)
                    found.append(data)
        self.hits += len(hit_at)
        self.misses += len(miss_at)
        self.evictions += evictions
        return hit_at, found, miss_at, evictions

    def fill(self, blocks, payloads) -> None:
        """Store fetched payloads in the slots ``lookup_run`` reserved
        for them, where still reserved, without touching recency."""
        od = self._od
        for b, p in zip(blocks, payloads):
            if od.get(b) is RESERVED:
                od[b] = p

    def release(self, blocks) -> None:
        """Drop the still-reserved slots of ``blocks`` (a failed read)."""
        od = self._od
        for b in blocks:
            if od.get(b) is RESERVED:
                del od[b]

    def counters(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}


#: "never used again inside the replayed window" sentinel for oracle
#: next-use times.  Large enough to dominate any real batch index while
#: staying safely inside int64 when negated for max-heap ordering.
FAR_NEXT_USE = 1 << 62


class OracleCache:
    """Belady (optimal) eviction over block IDs, driven by a replayed
    sampler schedule.

    Same live-cache surface and hit/miss/eviction counters as
    ``LRUCache`` (``access``/``access_run``/``get``/``peek``/``put``/
    ``counters``), but the victim on overflow is the resident block whose
    *next use* — known ahead of time because the sampler's id stream is
    seed-deterministic and replayed one window ahead — is farthest in the
    future (``FAR_NEXT_USE`` if never reused inside the window).

    Schedule delivery is two-phase per batch (``begin_batch``): the
    current batch's blocks are first protected at next-use == *now* for
    the batch's duration (so intra-batch reuse never loses to a block
    with a scheduled future use), and their true after-this-batch
    next-use times are applied when the following batch begins.  The
    batch is the scheduling quantum: below one batch's unique-block
    working set the whole residency turns over every batch and no
    batch-granular policy can beat recency — Belady's advantage needs
    capacities that hold at least a batch (the policy sweep's floor).
    Without any schedule the cache degrades to FIFO — a quality
    fallback only; reads stay correct either way.
    """

    def __init__(self, capacity_blocks: int):
        self.capacity = max(1, int(capacity_blocks))
        self._data: dict[int, object] = {}   # resident payloads (ins. order)
        self._nu: dict[int, int] = {}        # scheduled next use (abs. batch)
        self._heap: list[tuple[int, int, int]] = []  # (-next_use, seq, bid)
        self._latest: dict[int, int] = {}    # bid -> authoritative heap seq
        self._seq = 0                        # heap tiebreak: FIFO among ties
        self._pending: tuple[np.ndarray, np.ndarray] | None = None
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- schedule delivery --------------------------------------------------
    def _push(self, bid: int) -> None:
        """(Re-)insert ``bid``'s authoritative heap entry at its current
        priority; older entries for the same bid turn stale (lazy)."""
        import heapq
        heap = self._heap
        if len(heap) > max(1024, 16 * self.capacity):
            # lazy entries dominate: rebuild from the residents
            heap[:] = [(-self._next_use_of(b), s, b)
                       for b, s in self._latest.items()]
            heapq.heapify(heap)
        heapq.heappush(heap, (-self._next_use_of(bid), self._seq, bid))
        self._latest[bid] = self._seq
        self._seq += 1

    def _set(self, bid: int, next_use: int) -> None:
        if next_use >= FAR_NEXT_USE:
            self._nu.pop(bid, None)
        else:
            self._nu[bid] = next_use
        if bid in self._data:
            self._push(bid)

    def begin_batch(self, idx: int, blocks: np.ndarray,
                    next_use: np.ndarray) -> None:
        """Enter batch ``idx``: apply the previous batch's deferred
        after-batch next-use times, then protect this batch's ``blocks``
        at next-use == ``idx`` (the nearest possible time — intra-batch
        reuse must never lose to a block with a scheduled future use)
        and defer their ``next_use`` (first use *after* ``idx``) to the
        next call."""
        if self._pending is not None:
            for b, v in zip(*self._pending):
                self._set(int(b), int(v))
        for b in blocks:
            self._set(int(b), int(idx))
        self._pending = (blocks, next_use)

    def _next_use_of(self, bid: int) -> int:
        return self._nu.get(bid, FAR_NEXT_USE)

    def _evict_one(self) -> tuple[int, object]:
        """Pop the resident block with the farthest next use (lazy
        max-heap: stale entries — evicted blocks or superseded
        priorities — are skipped; FIFO among equal next-use)."""
        import heapq
        heap = self._heap
        while heap:
            _, seq, bid = heapq.heappop(heap)
            if bid in self._data and seq == self._latest.get(bid):
                self._latest.pop(bid, None)
                return bid, self._data.pop(bid)
        bid = next(iter(self._data))             # unreachable fallback
        self._latest.pop(bid, None)
        return bid, self._data.pop(bid)

    # -- trace-replay path --------------------------------------------------
    def access(self, block: int) -> bool:
        if block in self._data:
            self.hits += 1
            return True
        self.misses += 1
        self.put_new(block, None)
        return False

    def access_run(self, first: int, n: int) -> int:
        return sum(0 if self.access(first + i) else 1 for i in range(n))

    # -- live-cache path (payload-carrying) ---------------------------------
    def get(self, block: int):
        """Payload for ``block`` or None on miss (counts either way)."""
        if block in self._data:
            self.hits += 1
            return self._data[block]
        self.misses += 1
        return None

    def peek(self, block: int):
        """Payload if resident (no counters) — the post-fetch re-check of
        the sharded read path, where the fetch itself already counted."""
        return self._data.get(block)

    def put_new(self, block: int, payload) -> tuple[int, object] | None:
        evicted = None
        if block not in self._data and len(self._data) >= self.capacity:
            evicted = self._evict_one()
            self.evictions += 1
        self._data[block] = payload
        self._push(block)
        return evicted

    put = put_new

    def counters(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}


def select_pinned_blocks(g, budget_blocks: int, block_bytes: int = 4096,
                         entry_bytes: int = EDGE_ENTRY_BYTES
                         ) -> dict[int, object]:
    """Greedy hottest-first pinning: walk nodes in descending degree and
    claim each one's blocks until ``budget_blocks`` is exhausted.  Heat =
    node degree — in GraphSAGE sampling the probability a node's neighbor
    list is read at hop t>0 is proportional to its in-degree, so hub
    blocks dominate the power-law request stream.  ``g`` needs
    ``degrees()`` and ``edge_byte_range(u, entry_bytes)``.  Returns
    ``{block_id: None}`` (payloads staged later)."""
    heat_order = np.argsort(-g.degrees())
    pinned: dict[int, object] = {}
    for u in heat_order:
        lo, hi = g.edge_byte_range(int(u), entry_bytes)
        blocks = range(lo // block_bytes, max(hi - 1, lo) // block_bytes + 1)
        if len(pinned) + len(blocks) > budget_blocks:
            break
        pinned.update((b, None) for b in blocks)
    return pinned


class PinnedCache:
    """User-space scratchpad: part of the capacity (half by default)
    statically *pins* the hottest blocks, the rest is an app-managed LRU
    for short-term reuse.  This is the "manually orchestrate
    high-locality data movements" runtime of §IV-C: same DRAM budget as a
    page cache, but informed placement and no kernel maintenance costs.
    """

    def __init__(self, g, capacity_blocks: int, block_bytes: int = 4096,
                 entry_bytes: int = EDGE_ENTRY_BYTES,
                 pinned_budget: int | None = None):
        """``g`` needs ``degrees()`` and ``edge_byte_range(u, entry_bytes)``
        — a ``CSRGraph`` or any store exposing the same index (the live
        ``DiskStore`` passes a view over its in-memory ``indptr``).

        ``pinned_budget`` caps how many blocks may be pinned (default:
        half the capacity).  A budget exceeding the capacity raises —
        pins are never silently evicted to make room."""
        capacity_blocks = max(2, int(capacity_blocks))
        if pinned_budget is None:
            pinned_budget = capacity_blocks // 2
        if pinned_budget > capacity_blocks:
            raise ValueError(
                f"pinned budget {pinned_budget} exceeds cache capacity "
                f"{capacity_blocks} blocks; pins are never evicted, so "
                "shrink the pinned set or grow the cache")
        self._pinned = select_pinned_blocks(g, pinned_budget, block_bytes,
                                            entry_bytes)
        self._lru = LRUCache(capacity_blocks - len(self._pinned))
        self._pinned_hits = 0

    def access(self, block: int) -> bool:
        if block in self._pinned:
            self._pinned_hits += 1
            return True
        return self._lru.access(block)

    def access_run(self, first: int, n: int) -> int:
        return sum(0 if self.access(first + i) else 1 for i in range(n))

    # -- live-cache path (payload-carrying) ---------------------------------
    def get(self, block: int):
        """Payload for ``block`` or None on miss.  A pinned block whose
        payload has not been loaded yet counts as a miss exactly once (the
        caller fetches and ``put``s it; it is never evicted after that)."""
        if block in self._pinned:
            payload = self._pinned[block]
            if payload is not None:
                self._pinned_hits += 1
                return payload
            self._lru.misses += 1
            return None
        return self._lru.get(block)

    def put(self, block: int, payload) -> tuple[int, object] | None:
        if block in self._pinned:
            self._pinned[block] = payload
            return None                          # pins never displace
        return self._lru.put(block, payload)

    @property
    def hits(self) -> int:
        return self._pinned_hits + self._lru.hits

    @property
    def misses(self) -> int:
        return self._lru.misses

    @property
    def evictions(self) -> int:
        return self._lru.evictions

    def counters(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}
