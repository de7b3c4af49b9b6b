"""Device-resident array caches: the HBM analogue of the DiskStore's
page cache, one design instantiated per array family.

``DeviceArrayCache`` is the generic tier: a fixed-capacity ``(C, W)``
HBM-resident entry cache (held as ``(C, 1, Wp)`` rows, the layout of
``kernels.layout``) plus a device-side ``entry_id -> slot``
indirection table, with **host-managed, batched** admission/eviction —
the LRU/pinned bookkeeping is vectorized numpy over whole id batches
(stamp arrays + argpartition victim selection), not a per-id Python
loop, so admission overhead stays flat into the 10-100k
unique-entries-per-batch regime (measured by the benchmark's
``--admission-bench`` rows).  The default pinned policy stages the
hottest entries permanently, per the paper's skewed-access
characterization: hub structures dominate power-law request streams.

Two instantiations — the cache is keyed by *(array, entry id)*, and
"entry" means whatever unit that array is read in:

* ``DeviceFeatureCache`` — entries are feature *rows* ``(rows, F)
  float32``; the batch's unique node ids resolve against the host
  mirror, misses batch-fetch through the backing ``GraphStore``
  (in-memory arrays **or** real paged ``DiskStore`` reads) and scatter
  into victim slots by one jit-compiled update, and the rows are
  gathered **on device** by the ``feature_gather_cached`` Pallas kernel.
* ``DeviceEdgeBlockCache`` — entries are CSR *edge blocks*
  ``(blocks, BLOCK_E) int32`` of the (padded) ``indices`` array: the
  ``neighbor_sample_cached`` kernel reads its two per-target edge blocks
  through the same slot indirection, so the sampling kernel too runs
  beyond HBM (the out-of-core *topology* path).  ``plan`` chunks a
  frontier so each kernel dispatch's block working set fits the
  non-pinned budget.

Residency contract: ids are resolved in segments whose non-pinned count
never exceeds the LRU capacity.  Hits are re-stamped *before* victims
are selected and victims are the oldest-stamped non-pinned slots, so by
the time a segment (or a planned sampling chunk) is dispatched every one
of its entries is resident — even when the batch's working set exceeds
the whole cache.  Bit-identity: entries cross host->device with
unchanged bits and the scatter/gather paths copy them verbatim, so
cached training matches the full-upload path exactly at equal seeds.

Admission is *staged* (``plan_rows`` -> ``fetch_plan`` ->
``execute_plan``): planning is serialized mirror bookkeeping that
reserves victim slots, fetching is lock-free backing I/O callable from
any thread, and execution replays installs+gathers in plan order on a
single lane — the decomposition the overlapped loader
(``core.pipeline.OverlappedLoader``) spreads across its miss-resolve
and admit lanes while staying bit-identical to the synchronous path.
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import numpy as np

from repro.kernels.layout import row_width, to_rows
from repro.obs import names as obs_names
from repro.storage.specs import DEFAULT, DeviceCacheSpec


def pad_pow2(arr: np.ndarray, fill) -> np.ndarray:
    """Pad a 1-D/2-D array's leading dim up to the next power of two with
    ``fill`` rows — the shared recompile-bounding bucketing: dispatch and
    scatter widths vary batch to batch, and unbucketed shapes would
    compile one kernel per distinct length."""
    n = arr.shape[0]
    width = 1 << (n - 1).bit_length()
    if width == n:
        return arr
    pad = np.broadcast_to(fill, (width - n,) + arr.shape[1:])
    return np.concatenate([arr, pad])


# the kernels own the edge-array pad rule; re-exported here because the
# block cache's public surface is the storage package
from repro.kernels.neighbor_sample import edge_block_count  # noqa: E402


@dataclasses.dataclass
class _PlanSegment:
    """One residency-contract segment of an ``AdmissionPlan``: which ids
    it serves, which of them miss, the victim slots reserved for the
    installs, and (after the fetch stage) the fetched payloads."""

    ids: np.ndarray                     # segment ids (dispatch pads incl.)
    miss_ids: np.ndarray
    slots: np.ndarray                   # install slots for miss_ids
    evict_ids: np.ndarray
    rows: np.ndarray | None = None      # miss payloads, set by the fetch
    hits: int = 0                       # counted-request counters
    misses: int = 0
    evictions: int = 0


@dataclasses.dataclass
class AdmissionPlan:
    """A batch's cache admission, decided but not yet performed.

    Produced by ``plan_rows`` (serialized host-mirror bookkeeping),
    fed through ``fetch_plan`` (backing-store reads, lock-free, any
    thread), consumed by ``execute_plan``/``install_plan`` (ordered
    device mutation).  ``counters`` is this plan's exact counted
    hit/miss/eviction/upload bill — attributed per plan rather than by
    global deltas, so concurrent stages of different batches never
    bleed into each other's accounting.

    Reserved-slot handoff: planning stamps every planned id at the MRU
    end of the mirror and assigns victim slots immediately, so a later
    batch's plan can only evict what this plan no longer needs; because
    installs+gathers replay strictly in plan order on a single lane,
    an in-flight batch's rows are never evicted before its gather.

    ``generation`` pins the plan to the mirror state it was made
    against: a ``reset()`` (pipeline-restart recovery) bumps the cache
    generation, and executing a plan from a previous generation raises
    ``StaleAdmissionPlan`` instead of scattering into slots whose
    reservations no longer exist."""

    segments: list
    counters: dict
    generation: int = 0


class StaleAdmissionPlan(RuntimeError):
    """An ``AdmissionPlan`` outlived a cache ``reset()``: its reserved
    slots refer to a discarded mirror state.  Raised by the install/
    execute stages so an orphaned pipeline lane can never corrupt the
    post-restart cache."""


class DeviceArrayCache:
    """Generic HBM entry cache over one backing array, keyed by entry id.

    Subclasses supply the array geometry (``num_entries`` entries of
    ``width`` elements), a ``fetch(ids) -> (n, width)`` miss reader, and
    a per-entry ``heat`` vector for pinned placement.  This class owns
    the device state (``table``/``slot_of``), the vectorized host
    mirror, the admission/eviction policy, and the counters."""

    entry_noun = "entries"

    def __init__(self, *, array: str, num_entries: int, width: int, dtype,
                 fetch, heat=None, capacity: int, policy: str = "lru",
                 pinned_fraction: float = 0.5):
        import jax
        import jax.numpy as jnp

        self.array = array
        self.capacity = int(capacity)
        self.policy = policy
        if self.policy not in ("lru", "pinned", "optimal"):
            raise ValueError(f"unknown device-cache policy {self.policy!r};"
                             " have ('lru', 'pinned', 'optimal')")
        if self.capacity < 1:
            raise ValueError(
                f"device {array} cache needs at least one {self.entry_noun}")
        n = int(num_entries)
        W = int(width)
        self.num_entries, self.width = n, W
        self._fetch = fetch
        self._itemsize = np.dtype(dtype).itemsize
        self._jnp = jnp
        self._lock = threading.Lock()
        self.hits = self.misses = self.evictions = 0
        self.preload_rows = 0
        self.bytes_uploaded = 0
        self._generation = 0
        self.resets = 0

        if self.policy == "pinned":
            if self.capacity < 2:
                raise ValueError(
                    f"pinned policy needs capacity >= 2 {self.entry_noun} "
                    "(use policy='lru' for degenerate caches)")
            pin_budget = int(round(self.capacity * pinned_fraction))
            if pin_budget > self.capacity:
                raise ValueError(
                    f"pinned budget {pin_budget} exceeds cache capacity "
                    f"{self.capacity} {self.entry_noun}; pins are never "
                    "evicted, so shrink pinned_fraction or grow the cache")
            heat = np.asarray(heat if heat is not None else np.zeros(n))
            order = np.argsort(-heat, kind="stable")
            pinned_ids = np.sort(order[:min(pin_budget, n)]).astype(np.int64)
        else:
            pinned_ids = np.empty(0, np.int64)
        self._pinned_ids = pinned_ids
        self._pinned_mask = np.zeros(n + 1, bool)
        self._pinned_mask[pinned_ids] = True
        self._lru_capacity = self.capacity - pinned_ids.size
        if self._lru_capacity < 1:
            raise ValueError(
                f"pinned set ({pinned_ids.size} {self.entry_noun}) leaves "
                f"no LRU slots in a {self.capacity}-{self.entry_noun} "
                "cache; lower pinned_fraction or grow the cache")

        # vectorized host mirror: id -> slot, slot -> id/stamp/pinned
        self._host_slot = np.full(n + 1, -1, np.int64)
        self._slot_entry = np.full(self.capacity, -1, np.int64)
        self._slot_stamp = np.zeros(self.capacity, np.int64)
        self._slot_pinned = np.zeros(self.capacity, bool)
        self._free = np.arange(self.capacity)
        self._free_ptr = 0              # slots [_free_ptr:] still free
        self._clock = 0

        # Belady state (policy='optimal'): per-entry next-use times fed
        # by the replay lane (storage.oracle), batch-granular.  Entries
        # with no scheduled reuse sit at FAR_NEXT_USE — prime victims.
        if self.policy == "optimal":
            from repro.storage.blockdev import FAR_NEXT_USE
            self._far = FAR_NEXT_USE
            self._next_use = np.full(n + 1, FAR_NEXT_USE, np.int64)
            self._oracle_updates: dict[int, tuple] = {}
            self._oracle_pending: tuple | None = None

        # device state: +1 indirection entry — index n is the
        # scatter-padding sentinel, never queried by a real id
        self.slot_of = jnp.full((n + 1,), -1, jnp.int32)
        # the kernels' (C, 1, Wp) row layout; rows cross host->device at
        # their logical width and are padded on device
        self.table = jnp.zeros((self.capacity, 1, row_width(W)), dtype)
        donate = (0, 1) if jax.default_backend() == "tpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate)
        def _update(table, slot_of, slots, rows, evict_ids, new_ids):
            table = table.at[slots].set(to_rows(rows))
            slot_of = slot_of.at[evict_ids].set(-1)
            slot_of = slot_of.at[new_ids].set(slots)
            return table, slot_of

        self._update = _update
        if pinned_ids.size:
            self._preload_pinned()

    # -- admission / eviction (host-managed, batched) ------------------------
    def _preload_pinned(self) -> None:
        """Stage the pinned hot entries eagerly (the §IV-C runtime stages
        its scratchpad before training starts).  The fetches are real
        backing reads but count as ``preload_rows``, not misses.
        Delta-based so a post-``reset`` re-preload leaves the cumulative
        hit/miss/eviction counters untouched."""
        with self._lock:
            h, m, e = self.hits, self.misses, self.evictions
            self._resolve(self._pinned_ids)
            self._slot_pinned[self._host_slot[self._pinned_ids]] = True
            self.preload_rows += self.misses - m
            self.hits, self.misses, self.evictions = h, m, e

    def _segments(self, ids: np.ndarray):
        """Split ``ids`` (order preserved) so each segment's non-pinned
        count fits the LRU capacity — the residency contract: a segment's
        installs can then only evict entries outside the segment, never
        one between its resolution and its gather."""
        nonpinned = np.flatnonzero(~self._pinned_mask[ids])
        cuts = nonpinned[self._lru_capacity::self._lru_capacity]
        if cuts.size == 0:
            yield ids
            return
        yield from np.split(ids, cuts)

    def _plan_segment(self, seg: np.ndarray,
                      counted: int | None = None) -> _PlanSegment:
        """Decide residency for every id in ``seg``, in one batched pass
        of host-mirror bookkeeping: stamp hits at the MRU end, pick
        victim slots for all misses at once (free slots first, then the
        oldest-stamped non-pinned slots), and record the miss ids +
        reserved slots in the returned ``_PlanSegment`` — the fetch and
        the device scatter happen later (``_fetch_segment`` /
        ``_install_segment``), possibly on other threads/lanes.  The
        mirror is updated *here*, so consecutive plans compose exactly
        like consecutive synchronous resolves.  Caller holds the lock.

        Only the first ``counted`` ids contribute to the hit/miss/
        eviction counters (default: all) — positions beyond that are
        dispatch filler, kept resident for the kernel but excluded from
        the metrics so reported hit rates reflect real requests only."""
        if counted is None:
            counted = seg.size
        slots = self._host_slot[seg]
        hit_mask = slots >= 0
        hit_slots = slots[hit_mask]
        # hits move to the MRU end *before* victim selection, preserving
        # the sequential-LRU outcome for the whole segment at once
        self._slot_stamp[hit_slots] = self._clock + np.arange(hit_slots.size)
        self._clock += int(hit_slots.size)
        # a repeated id (the loader's pow2 dispatch padding) must install
        # exactly once: only the first occurrence of a missing id is a
        # real miss — later copies are resident by dispatch time (hits).
        # Double-installing would leave ghost slots whose eviction clears
        # slot_of[id] while the id still looks resident.
        order = np.argsort(seg, kind="stable")
        dup = np.zeros(seg.size, bool)
        dup[order[1:]] = seg[order][1:] == seg[order][:-1]
        miss_mask = ~hit_mask & ~dup
        miss_ids = seg[miss_mask]
        n_hit = int(np.count_nonzero((hit_mask | (~hit_mask & dup))
                                     [:counted]))
        n_miss_counted = int(np.count_nonzero(miss_mask[:counted]))
        self.hits += n_hit
        self.misses += n_miss_counted
        ps = _PlanSegment(ids=seg, miss_ids=miss_ids,
                          slots=np.empty(0, np.int64),
                          evict_ids=np.empty(0, np.int64),
                          hits=n_hit, misses=n_miss_counted)
        m = int(miss_ids.size)
        if m == 0:
            return ps

        n_free = self.capacity - self._free_ptr
        take = min(n_free, m)
        new_slots = self._free[self._free_ptr:self._free_ptr + take]
        self._free_ptr += take
        n_evict = m - take
        if n_evict:
            if self.policy == "optimal":
                # Belady: evict the resident entries whose next use is
                # farthest (batched lexsort, no per-id loop).  The current
                # segment's hits are hard-masked — they must survive until
                # the segment's gather regardless of schedule — and the
                # stamp breaks next-use ties, so with no schedule fed the
                # selection degrades to exact LRU.  The residency
                # contract guarantees enough non-current candidates:
                # capacity >= segment hits + misses.
                cand = (self._slot_entry >= 0) & ~self._slot_pinned
                cand[hit_slots] = False
                occupied = np.flatnonzero(cand)
                nu = self._next_use[self._slot_entry[occupied]]
                order = np.lexsort((self._slot_stamp[occupied], -nu))
                oldest = occupied[order[:n_evict]]
            else:
                occupied = np.flatnonzero((self._slot_entry >= 0)
                                          & ~self._slot_pinned)
                oldest = occupied[np.argpartition(
                    self._slot_stamp[occupied], n_evict - 1)[:n_evict]]
            victims = self._slot_entry[oldest]
            self._host_slot[victims] = -1
            self._slot_entry[oldest] = -1
            new_slots = np.concatenate([new_slots, oldest])
            ps.evict_ids = victims
            # counted misses consume free slots first (they are a prefix
            # of the segment), so only their overflow displaces entries
            ps.evictions = min(n_evict, max(0, n_miss_counted - n_free))
            self.evictions += ps.evictions
        self._slot_stamp[new_slots] = self._clock + np.arange(m)
        self._clock += m
        self._host_slot[miss_ids] = new_slots
        self._slot_entry[new_slots] = miss_ids
        ps.slots = new_slots
        return ps

    def _fetch_segment(self, ps: _PlanSegment) -> None:
        """Pull a planned segment's miss payloads from the backing store.
        Touches no cache state (host mirror or device), so it is safe on
        any thread, concurrently with planning and installs — this is
        the piece the overlapped loader runs in its resolve lane."""
        if ps.miss_ids.size:
            ps.rows = np.ascontiguousarray(self._fetch(ps.miss_ids))

    def _install_segment(self, ps: _PlanSegment) -> None:
        """Scatter a fetched segment into its reserved slots.  Device
        mutations must replay in plan order (single lane) — that is what
        keeps the device ``slot_of``/``table`` tracking the host mirror
        and makes the staged path bit-identical to the synchronous one."""
        if ps.miss_ids.size:
            self._push(ps.miss_ids, ps.slots, ps.evict_ids, ps.rows)
            ps.rows = None              # free the host copy

    def _resolve(self, seg: np.ndarray, counted: int | None = None) -> None:
        """Make every id in ``seg`` resident, synchronously: plan, fetch,
        install in one call (the unstaged path)."""
        ps = self._plan_segment(seg, counted)
        self._fetch_segment(ps)
        self._install_segment(ps)

    def _push(self, miss_ids, miss_slots, evict_ids, rows) -> None:
        """One jitted scatter installs the fetched entries and repairs the
        indirection table.  Update lengths are padded to powers of two
        (pad rows rewrite the last slot, pad ids hit the sentinel entry)
        so retracing stays bounded across batch-to-batch miss counts."""
        jnp = self._jnp
        m = len(miss_ids)
        width = 1 << (m - 1).bit_length()
        sent = self.num_entries
        slots = pad_pow2(np.asarray(miss_slots, np.int32), miss_slots[-1])
        new_ids = pad_pow2(np.asarray(miss_ids, np.int32), sent)
        ev = np.concatenate([np.asarray(evict_ids, np.int32),
                             np.full(width - len(evict_ids), sent, np.int32)])
        rows = pad_pow2(rows, rows[-1])
        self.table, self.slot_of = self._update(
            self.table, self.slot_of, jnp.asarray(slots), jnp.asarray(rows),
            jnp.asarray(ev), jnp.asarray(new_ids))
        self.bytes_uploaded += int(m) * self.width * self._itemsize

    # -- staged admission (the overlapped pipeline's three lanes) ------------
    def plan_rows(self, ids: np.ndarray,
                  n_valid: int | None = None) -> AdmissionPlan:
        """Stage one of admission: serialized host-mirror bookkeeping for
        ``ids`` (segmented by the residency contract), under the lock,
        with nothing fetched or uploaded yet.  Plans MUST be created in
        batch order and executed in the same order — the plan records
        reserved victim slots against the mirror state at plan time.
        ``n_valid`` marks trailing ids as dispatch padding (excluded
        from the counters, like ``gather_rows``)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        nv = ids.size if n_valid is None else int(n_valid)
        plan = AdmissionPlan(segments=[],
                             counters=dict.fromkeys(
                                 obs_names.DEVCACHE_KEYS, 0))
        offset = 0
        with self._lock:
            plan.generation = self._generation
            for seg in self._segments(ids):
                if seg.size == 0:
                    continue
                ps = self._plan_segment(seg, counted=max(
                    0, min(seg.size, nv - offset)))
                offset += seg.size
                plan.segments.append(ps)
                plan.counters["hits"] += ps.hits
                plan.counters["misses"] += ps.misses
                plan.counters["evictions"] += ps.evictions
                plan.counters["bytes_uploaded"] += (
                    int(ps.miss_ids.size) * self.width * self._itemsize)
        return plan

    def fetch_plan(self, plan: AdmissionPlan) -> AdmissionPlan:
        """Stage two: pull every planned segment's miss payloads from the
        backing store.  Lock-free and device-free — safe on any thread,
        overlapping other batches' planning, installs, and compute."""
        for ps in plan.segments:
            self._fetch_segment(ps)
        return plan

    def check_generation(self, plan: AdmissionPlan) -> None:
        """Refuse to perform device mutations for a plan made against a
        pre-``reset`` mirror (its slot reservations are gone)."""
        if plan.generation != self._generation:
            raise StaleAdmissionPlan(
                f"device {self.array} cache: plan from generation "
                f"{plan.generation} cannot install into generation "
                f"{self._generation} (cache was reset)")

    def install_plan(self, plan: AdmissionPlan) -> None:
        """Stage three: scatter the fetched segments into their reserved
        slots, strictly in plan order, from a single lane."""
        self.check_generation(plan)
        for ps in plan.segments:
            self._install_segment(ps)

    def reset(self, *, preload: bool = True) -> None:
        """Drop every entry and rebuild the mirror from scratch — the
        recovery hook for abandoned in-flight plans.  A pipeline restart
        (or a fetch that failed beyond the retry policy) can leave plans
        that reserved mirror slots whose device rows were never
        installed: those ids look resident but their slots hold stale
        bits.  Rather than repair reservations plan by plan, restart the
        cache empty — values are unaffected (the cache is a pure
        performance tier), only future hit/miss counters shift.  Bumps
        the generation so any surviving plan fails loudly at install."""
        jnp = self._jnp
        with self._lock:
            self._generation += 1
            self.resets += 1
            n = self.num_entries
            self._host_slot = np.full(n + 1, -1, np.int64)
            self._slot_entry = np.full(self.capacity, -1, np.int64)
            self._slot_stamp = np.zeros(self.capacity, np.int64)
            self._slot_pinned = np.zeros(self.capacity, bool)
            self._free = np.arange(self.capacity)
            self._free_ptr = 0
            self._clock = 0
            # stale table payloads are unreachable once slot_of is cleared
            self.slot_of = jnp.full((n + 1,), -1, jnp.int32)
        if preload and self._pinned_ids.size:
            self._preload_pinned()

    # -- read paths ----------------------------------------------------------
    def resolve(self, ids: np.ndarray) -> None:
        """Admission without a gather: make ``ids`` resident (segmented by
        the residency contract).  The sampling kernel reads the entries
        through ``table``/``slot_of`` itself.  Unstaged — the edge-block
        cache is owned entirely by the sampling lane, which resolves and
        dispatches within one thread."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        with self._lock:
            for seg in self._segments(ids):
                if seg.size:
                    self._resolve(seg)

    # -- oracle (Belady) schedule delivery -----------------------------------
    def oracle_feed(self, updates: dict) -> None:
        """Accept per-batch next-use updates from the replay lane:
        ``{batch_idx: (entry_ids, next_use)}`` where ``next_use[j]`` is
        the first batch index *after* ``batch_idx`` at which
        ``entry_ids[j]`` is requested again (``FAR_NEXT_USE`` if never
        inside the replayed window).  Only valid under
        ``policy='optimal'``."""
        if self.policy != "optimal":
            raise ValueError(
                f"oracle_feed on a {self.policy!r}-policy device cache")
        with self._lock:
            self._oracle_updates.update(updates)

    def oracle_begin_batch(self, idx: int) -> None:
        """Enter batch ``idx`` (called once per batch, in batch order,
        from the lane that owns this cache).  Two-phase application: the
        previous batch's deferred after-batch next-use times land first,
        then this batch's entries are protected at next-use == ``idx``
        for the batch's duration (so intra-batch reuse never loses a
        victim race to an entry with a scheduled future use); their true
        after-``idx`` times are deferred to the next call.  A batch with
        no schedule (replay behind, or a restart replay) is a no-op —
        eviction then falls back to the stamp tiebreak (exact LRU)."""
        if self.policy != "optimal":
            return
        with self._lock:
            if self._oracle_pending is not None:
                ids, nu = self._oracle_pending
                self._next_use[ids] = nu
                self._oracle_pending = None
            upd = self._oracle_updates.pop(idx, None)
            if upd is not None:
                ids, nu = upd
                self._next_use[ids] = idx
                self._oracle_pending = (ids, nu)

    # -- accounting ----------------------------------------------------------
    def counters(self) -> dict:
        # keyed by the canonical metric-name table's leaf keys
        with self._lock:
            return {k: getattr(self, k) for k in obs_names.DEVCACHE_KEYS}

    def stats(self) -> dict:
        return {"array": self.array, "policy": self.policy,
                "capacity_rows": self.capacity,
                "pinned_rows": int(self._pinned_ids.size),
                "resets": self.resets,
                **self.counters()}


class DeviceFeatureCache(DeviceArrayCache):
    """HBM-resident hot-row cache over a ``GraphStore`` feature table."""

    entry_noun = "rows"

    def __init__(self, backing, *, rows: int | None = None,
                 policy: str | None = None,
                 pinned_fraction: float | None = None,
                 spec: DeviceCacheSpec = DEFAULT.devcache):
        """``backing`` is anything with ``num_nodes`` / ``feat_dim`` /
        ``degrees()`` / ``gather_features(ids)`` — a ``CSRGraph``, an
        ``InMemoryStore``, or a ``DiskStore`` (then every miss is a real
        paged disk read and shows up in the store's I/O counters).  Heat
        for the pinned policy is node degree: hub rows dominate the
        gather stream in power-law graphs."""
        import jax.numpy as jnp
        from repro.kernels import ops

        self.backing = backing
        self._ops = ops
        n = int(backing.num_nodes)
        F = int(backing.feat_dim)
        self.num_nodes, self.feat_dim = n, F
        super().__init__(
            array="features", num_entries=n, width=F, dtype=jnp.float32,
            fetch=lambda ids: np.ascontiguousarray(
                backing.gather_features(np.asarray(ids, np.int64)),
                np.float32),
            heat=backing.degrees(),
            capacity=int(spec.rows if rows is None else rows),
            policy=policy or spec.policy,
            pinned_fraction=(spec.pinned_fraction if pinned_fraction is None
                             else pinned_fraction))

    def execute_plan(self, plan: AdmissionPlan):
        """Admit-and-gather lane: install each fetched segment and gather
        it on device, strictly in plan order.  Interleaving install(k) ->
        gather(k) -> install(k+1) replays exactly the synchronous
        ``gather_rows`` sequence (a later segment's installs may evict an
        earlier segment's rows, but only after that segment's gather),
        so values, counters, and eviction outcomes are bit-identical."""
        self.check_generation(plan)
        jnp = self._jnp
        parts = []
        for ps in plan.segments:
            self._install_segment(ps)
            # pad the dispatch length with a resident id so the
            # kernel's compiled-shape count stays logarithmic
            n = ps.ids.size
            seg = pad_pow2(ps.ids, ps.ids[-1])
            parts.append(self._ops.feature_gather_cached(
                self.table, self.slot_of, jnp.asarray(seg, jnp.int32),
                width=self.feat_dim)[:n])
        if not parts:
            return jnp.zeros((0, self.feat_dim), jnp.float32)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    def gather_rows(self, ids: np.ndarray, n_valid: int | None = None):
        """ids: (U,) host node ids -> (U, F) float32 device array, gathered
        on-device through the cache; misses are admitted along the way.
        Works for any U, including U > capacity (segmented residency).

        ``n_valid`` marks trailing ids as dispatch padding (the loader's
        pow2 bucketing): they are resolved and gathered like any other
        id but excluded from the hit/miss/eviction counters.

        This is the synchronous composition of the staged API — plan
        (mirror bookkeeping) -> fetch (backing reads) -> execute (install
        + device gather); the overlapped loader runs the same three
        calls from separate pipeline lanes."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size == 0:
            return self._jnp.zeros((0, self.feat_dim), self._jnp.float32)
        plan = self.plan_rows(ids, n_valid=n_valid)
        self.fetch_plan(plan)
        return self.execute_plan(plan)


class DeviceEdgeBlockCache(DeviceArrayCache):
    """HBM-resident edge-*block* cache over the CSR topology arrays.

    Entries are ``block_e``-wide int32 chunks of the padded ``indices``
    array — exactly the unit the ``neighbor_sample`` kernel stages per
    target (two consecutive blocks cover any neighbor list with
    ``max_degree <= block_e``).  The cached sampling kernel looks each
    block up through ``slot_of`` and DMAs the *cache* row, so the full
    edge array never crosses to the device: topology misses are fetched
    through the backing ``GraphStore`` (real paged reads over a
    ``DiskStore``) and admitted like feature rows.  Block heat for the
    pinned policy is the max degree of the nodes whose neighbor lists
    touch the block — hub lists make hub blocks."""

    entry_noun = "blocks"

    def __init__(self, backing, *, indptr, block_e: int,
                 blocks: int, policy: str = "lru",
                 pinned_fraction: float = 0.5):
        indptr = np.asarray(indptr, np.int64)
        self._indptr = indptr
        self.block_e = int(block_e)
        E = int(indptr[-1])
        nb = edge_block_count(E, self.block_e)
        self.num_blocks = nb
        # the kernel clamps a degree-0 tail target's base block here, so
        # the staged pair (max_block, max_block+1) always exists
        self.max_block = nb - 2
        deg = np.diff(indptr)
        heat = np.zeros(nb, np.int64)
        if deg.size:
            b0 = np.minimum(indptr[:-1] // self.block_e, self.max_block)
            np.maximum.at(heat, b0, deg)
            np.maximum.at(heat, b0 + 1, deg)
        import jax.numpy as jnp

        self.backing = backing
        super().__init__(
            array="topology", num_entries=nb, width=self.block_e,
            dtype=jnp.int32,
            fetch=lambda ids: np.ascontiguousarray(
                backing.gather_edge_blocks(np.asarray(ids, np.int64),
                                           self.block_e), np.int32),
            heat=heat, capacity=int(blocks), policy=policy,
            pinned_fraction=pinned_fraction)
        if self._lru_capacity < 4:
            raise ValueError(
                f"edge-block cache needs >= 4 non-pinned blocks (one "
                f"target's staged pair + the tile-padding pair); got "
                f"{self._lru_capacity} of {self.capacity} — grow the "
                "cache or lower pinned_fraction")

    def plan(self, targets: np.ndarray) -> list[tuple[slice, np.ndarray]]:
        """Chunk a flat frontier so each kernel dispatch's unique block
        working set fits the non-pinned budget (all of a dispatch's
        blocks must be resident simultaneously, unlike the row gather's
        per-segment residency).  Returns ``[(slice, block_ids), ...]``;
        every chunk's block list includes blocks (0, 1), which tile
        padding (node 0) dereferences."""
        t = np.asarray(targets, np.int64).reshape(-1)
        b0 = np.minimum(self._indptr[t] // self.block_e, self.max_block)
        budget = self._lru_capacity
        pinned = self._pinned_mask
        # fast path (the common case): the whole frontier's block set fits
        # one dispatch — vectorized, no per-target loop
        needed = np.unique(np.concatenate([b0, b0 + 1, [0, 1]]))
        if np.count_nonzero(~pinned[needed]) <= budget:
            return [(slice(0, t.size), needed)]
        chunks: list[tuple[slice, np.ndarray]] = []

        def fresh() -> tuple[set, int]:
            blk = {0, 1}
            return blk, sum(1 for b in blk if not pinned[b])

        blk, used = fresh()
        cur = 0
        for k in range(t.size):
            pair = (int(b0[k]), int(b0[k]) + 1)
            need = [b for b in pair if b not in blk]
            cost = sum(1 for b in need if not pinned[b])
            if used + cost > budget and k > cur:
                chunks.append((slice(cur, k),
                               np.fromiter(sorted(blk), np.int64)))
                blk, used = fresh()
                cur = k
                need = [b for b in pair if b not in blk]
                cost = sum(1 for b in need if not pinned[b])
            blk.update(need)
            used += cost
        chunks.append((slice(cur, t.size),
                       np.fromiter(sorted(blk), np.int64)))
        return chunks
