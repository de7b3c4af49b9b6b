"""GraphStore — the graph layer behind the unified data plane.

The paper's central claim (§III) is that GNN training can exceed DRAM
capacity by leaving the edge-list array and feature table on storage.
This module makes that real instead of simulated: a ``GraphStore``
protocol with two implementations,

* ``InMemoryStore``  — wraps today's ``CSRGraph`` (everything in DRAM);
* ``DiskStore``      — serves the same reads from a paged on-disk layout
  (one 4 KB-block-aligned binary file per array + a JSON manifest,
  written by ``save_graph``) through ``os.pread`` fronted by a *live*
  page cache reusing the ``LRUCache``/``PinnedCache`` policies from
  ``storage.blockdev`` — the same policies the trace-replay engines
  model, now with real payloads and hit/miss/eviction counters.

Only the (N+1)-entry ``indptr`` index stays resident (it is the CSR
row index — a few MB even at billion-edge scale); ``indices``,
``features`` and ``labels`` are read on demand in ``block_bytes`` units.
The samplers (``core.sampler``) and the host loader (``core.loader``)
issue every edge/feature/label read through the store's access methods,
so a ``SampleTrace`` produced over a ``DiskStore`` carries the *actual*
block-I/O counters of its batch (``SampleTrace.io``), and training with
``--graph-store disk --cache-mb B`` runs the paper's headline scenario —
a working set larger than the cache — end to end.

``CSRGraph`` itself implements the data-access half of the protocol
(``out_degrees`` / ``gather_edges`` / ``gather_features`` /
``gather_labels``), so existing call sites keep working unchanged;
the store classes add the IO-counter/stats half.
"""

from __future__ import annotations

import contextlib
import json
import mmap
import os
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Protocol, runtime_checkable

import numpy as np

from repro.core.graph import CSRGraph
from repro.obs import names as obs_names
from repro.obs import session as obs_session
from repro.storage.blockdev import (LRUCache, OracleCache,
                                    select_pinned_blocks)
from repro.storage.faults import FaultInjector, FaultSpec
from repro.storage.integrity import block_checksums, crc32c
from repro.storage.specs import DEFAULT, RetrySpec, SystemSpec

MANIFEST = "manifest.json"
FORMAT = "smartsage-graphstore"
# one logical block-ID namespace per backing file, so a single cache
# budget (and a single pinning policy) spans all arrays
_NS_STRIDE = 1 << 40
_ARRAY_ORDER = ("indptr", "indices", "features", "labels")
# O_DIRECT demands offset/length/buffer alignment to the device's logical
# block size; 512 is the floor every Linux block device accepts
_DIRECT_IO_ALIGN = 512


@runtime_checkable
class GraphStore(Protocol):
    """Everything the data plane needs from a graph, wherever it lives."""

    name: str

    @property
    def num_nodes(self) -> int: ...

    @property
    def num_edges(self) -> int: ...

    @property
    def feat_dim(self) -> int: ...

    def degrees(self) -> np.ndarray: ...

    def out_degrees(self, nodes: np.ndarray) -> np.ndarray: ...

    def neighbors(self, u: int) -> np.ndarray: ...

    def gather_edges(self, rows, offsets) -> np.ndarray: ...

    def gather_features(self, ids) -> np.ndarray: ...

    def gather_labels(self, ids) -> np.ndarray: ...

    def gather_edge_blocks(self, blocks, block_e: int) -> np.ndarray: ...

    def io_counters(self) -> dict: ...

    def stats(self) -> dict: ...

    def to_csr(self) -> CSRGraph: ...

    def close(self) -> None: ...


class InMemoryStore:
    """``GraphStore`` over a DRAM-resident ``CSRGraph`` (the baseline the
    paper's in-memory design point assumes).  Pure delegation; all IO
    counters stay zero — nothing ever leaves memory."""

    kind = "mem"

    def __init__(self, g: CSRGraph):
        self.g = g
        self.name = g.name

    @property
    def num_nodes(self) -> int:
        return self.g.num_nodes

    @property
    def num_edges(self) -> int:
        return self.g.num_edges

    @property
    def feat_dim(self) -> int:
        return self.g.feat_dim

    def degrees(self):
        return self.g.degrees()

    def out_degrees(self, nodes):
        return self.g.out_degrees(nodes)

    def neighbors(self, u):
        return self.g.neighbors(u)

    def gather_edges(self, rows, offsets):
        return self.g.gather_edges(rows, offsets)

    def gather_features(self, ids):
        return self.g.gather_features(ids)

    def gather_labels(self, ids):
        return self.g.gather_labels(ids)

    def gather_edge_blocks(self, blocks, block_e: int):
        return self.g.gather_edge_blocks(blocks, block_e)

    def io_counters(self) -> dict:
        return dict.fromkeys(IOContext.KEYS, 0)

    def stats(self) -> dict:
        return {"kind": self.kind, **self.io_counters()}

    def to_csr(self) -> CSRGraph:
        return self.g

    def close(self) -> None:
        pass


class IOContext:
    """One attribution scope for a ``DiskStore``'s I/O counters — typically
    one minibatch.  Reads performed while the context is installed
    (``DiskStore.io_attribution``) merge into it, *including* reads the
    store's pread pool runs on other threads on the installer's behalf,
    so ``counters()`` is the exact I/O bill of the scope no matter which
    threads served it.  Thread-safe: pool workers add concurrently."""

    # fault keys are flat here (and in ``io_counters``) so the existing
    # numeric-delta plumbing (``_io_delta``, epoch deltas) keeps working;
    # ``nest_fault_counters`` folds them into ``io["faults"]`` at trace
    # assembly.  Both tuples come from the canonical metric-name table
    # (``repro.obs.names``) — the store emits canonical leaf keys by
    # construction.
    FAULT_KEYS = obs_names.FAULT_KEYS
    KEYS = obs_names.STORE_IO_KEYS + FAULT_KEYS

    __slots__ = ("_lock", "_c", "batch")

    def __init__(self):
        self._lock = threading.Lock()
        self._c = dict.fromkeys(self.KEYS, 0)
        # telemetry attribution: the batch index this scope's reads
        # belong to (set by the loader), inherited by pool-thread pread
        # spans so they nest under their submitting batch in the trace
        self.batch: int | None = None

    def add(self, **deltas) -> None:
        with self._lock:
            c = self._c
            for k, v in deltas.items():
                c[k] += v

    def counters(self) -> dict:
        with self._lock:
            return dict(self._c)


class StoreReadError(RuntimeError):
    """A block read failed beyond the retry policy: every attempt errored,
    came back short, missed its deadline, or failed checksum verification.
    Deliberately *not* an OSError — by the time this raises, the retry
    loop has already consumed the transient-error budget, and callers
    (devcache bypass, pipeline degrade) treat it as a policy decision,
    not an I/O hiccup."""


def nest_fault_counters(io: dict | None) -> dict | None:
    """Fold the flat fault counters of an I/O bill into ``io['faults']``
    — the shape traces expose (``SampleTrace.io['faults']``).  Counters
    stay flat inside the store so plain numeric-delta arithmetic works;
    call this once at trace-assembly time."""
    if not io:
        return io
    faults = {k: io.pop(k) for k in IOContext.FAULT_KEYS if k in io}
    if faults:
        io["faults"] = faults
    return io


def _pad_to_block(f, block_bytes: int) -> int:
    """Zero-pad an open binary file to the next block boundary."""
    size = f.tell()
    pad = -size % block_bytes
    if pad:
        f.write(b"\0" * pad)
    return size


def save_graph(g: CSRGraph, path: str, *,
               block_bytes: int | None = None) -> dict:
    """Serialize ``g`` to the on-disk GraphStore layout.

    ``path`` becomes a directory holding one binary file per array —
    ``indptr.bin`` (int64), ``indices.bin`` (int32, the paper's
    capacity-dominant edge-list array), ``features.bin`` (float32
    row-major), ``labels.bin`` (int32) — each zero-padded to a
    ``block_bytes`` boundary, plus a small JSON manifest with dtypes,
    shapes, logical byte sizes, and one CRC32C per block of the padded
    file (``block_crc32c`` — what ``DiskStore(verify=True)`` checks
    every read against).  Returns the manifest dict.
    """
    block_bytes = block_bytes or DEFAULT.diskstore.block_bytes
    os.makedirs(path, exist_ok=True)
    arrays = {
        "indptr": g.indptr.astype(np.int64),
        "indices": g.indices.astype(np.int32),
    }
    if g.features is not None:
        arrays["features"] = np.ascontiguousarray(g.features, np.float32)
    if g.labels is not None:
        arrays["labels"] = g.labels.astype(np.int32)
    manifest = {
        "format": FORMAT, "version": 2, "name": g.name,
        "num_nodes": g.num_nodes, "num_edges": g.num_edges,
        "feat_dim": g.feat_dim, "block_bytes": block_bytes,
        "arrays": {},
    }
    if g.labels is not None:
        manifest["n_classes"] = int(g.labels.max()) + 1
    for key, arr in arrays.items():
        fname = f"{key}.bin"
        raw = arr.tobytes()
        padded = raw + b"\0" * (-len(raw) % block_bytes)
        with open(os.path.join(path, fname), "wb") as f:
            f.write(raw)
            nbytes = _pad_to_block(f, block_bytes)
        manifest["arrays"][key] = {
            "file": fname, "dtype": arr.dtype.name,
            "shape": list(arr.shape), "nbytes": nbytes,
            "block_crc32c": [int(c)
                             for c in block_checksums(padded, block_bytes)],
        }
    with open(os.path.join(path, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=2)
    return manifest


class DiskStore:
    """Out-of-core ``GraphStore``: block-granular ``pread`` behind a live
    page cache.

    Every access method resolves to byte ranges in the backing files,
    fetched in ``block_bytes`` units through one cache budget shared by
    all arrays (block IDs are namespaced per file).  ``policy='lru'``
    models the OS page cache; ``policy='pinned'`` is the paper's §IV-C
    user-space scratchpad — half the budget statically pins the
    hottest (highest-degree) edge blocks, preloaded at open, the rest is
    LRU; ``policy='optimal'`` is Belady eviction driven by a replayed
    sampler schedule (``storage.oracle`` — the Ginex-style offline
    oracle; the cache is then unsharded and fed via ``oracle_feed`` /
    ``oracle_advance``).  Counters (``io_counters``) record requests,
    block fetches,
    bytes fetched from disk, and the cache's hits/misses/evictions.

    Concurrency: the LRU budget is split into ``lock_shards``
    hashed-block shards, each behind its own lock, so concurrent
    producer workers only contend when they touch the same shard (the
    engines' shared-resource contention model, Fig. 17; the
    ``--contention-workers`` micro-benchmark measures the scaling).  The
    pinned set is immutable after the preload and served lock-free.

    ``io_threads > 1`` additionally opens a pread pool: multi-range
    gathers (``gather_features`` / ``gather_edges`` /
    ``gather_edge_blocks``) split their ranges into block-disjoint
    groups and read the groups concurrently — no disk block is shared
    across groups, so each block is fetched by exactly one task and the
    fetch counters stay exact.  Attribution follows the *submitter*: a
    pool read bills the ``IOContext`` installed on the thread that
    triggered it (``io_attribution``), which is what makes per-batch
    ``SampleTrace.io`` deltas exact under concurrent producers.

    Reads go block-batched (``_read_blocks``): a group of ranges (one
    range, a gather's pool task or serial read, a ``warm_nodes`` task)
    looks up its distinct blocks with one lock acquisition per shard,
    reads the missed ones as contiguous runs (one ``pread`` per run; the
    ``preads`` counter against ``block_fetches`` says how often runs
    coalesce) and bills the group once.  A store with a fault injector,
    ``direct_io`` or ``policy='optimal'`` reads block by block instead
    (``_read_range``): the injector and O_DIRECT act on single blocks,
    and the Belady cache has no batched lookup.
    """

    kind = "disk"

    def __init__(self, path: str, *, cache_mb: float | None = None,
                 policy: str | None = None, cache_blocks: int | None = None,
                 lock_shards: int | None = None,
                 io_threads: int | None = None,
                 verify: bool = False,
                 direct_io: bool = False,
                 retry: RetrySpec | None = None,
                 faults: FaultSpec | None = None,
                 spec: SystemSpec = DEFAULT):
        self.path = path
        with open(os.path.join(path, MANIFEST)) as f:
            self.manifest = json.load(f)
        if self.manifest.get("format") != FORMAT:
            raise ValueError(f"{path}: not a {FORMAT} directory")
        self.name = self.manifest["name"]
        self.block_bytes = int(self.manifest["block_bytes"])
        self.verify = bool(verify)
        self.retry = RetrySpec() if retry is None else retry
        if faults is not None and faults.bitflip_rate > 0 and not self.verify:
            raise ValueError(
                "faults.bitflip_rate > 0 without verify=True would corrupt "
                "training data silently; open the store with verify=True")
        self._injector = (FaultInjector(faults)
                          if faults is not None and faults.storage_active
                          else None)
        self._crc: dict[str, np.ndarray] | None = None
        if self.verify:
            missing = [k for k, a in self.manifest["arrays"].items()
                       if "block_crc32c" not in a]
            if missing:
                raise ValueError(
                    f"{path}: manifest records no block checksums for "
                    f"{missing} — the layout predates checksum support; "
                    "re-save it with save_graph() or open with verify=False")
            self._crc = {k: np.asarray(a["block_crc32c"], np.uint32)
                         for k, a in self.manifest["arrays"].items()}
        # store totals of the counters ``_bill`` adds: syscalls and faults
        self._read_totals = dict.fromkeys(("preads",) + IOContext.FAULT_KEYS,
                                          0)
        self.cache_mb = (spec.diskstore.cache_mb if cache_mb is None
                         else float(cache_mb))
        self.policy = policy or spec.diskstore.policy
        if self.policy not in ("lru", "pinned", "optimal"):
            raise ValueError(f"unknown cache policy {self.policy!r}; "
                             "have ('lru', 'pinned', 'optimal')")

        self._arrays = self.manifest["arrays"]
        self._ns = {k: i for i, k in enumerate(_ARRAY_ORDER)
                    if k in self._arrays}
        self._dtype = {k: np.dtype(a["dtype"])
                       for k, a in self._arrays.items()}
        self._tls = threading.local()
        self._open_backing_files(direct_io)
        self._batched = (self._injector is None and not self.direct_io
                         and self.policy != "optimal")

        # the CSR row index stays resident — it IS the index structure
        # (N+1 int64: a few MB even at the paper's billion-edge scale)
        n = int(self.manifest["num_nodes"])
        self.indptr = np.fromfile(
            os.path.join(path, self._arrays["indptr"]["file"]),
            dtype=self._dtype["indptr"], count=n + 1)

        if cache_blocks is None:
            cache_blocks = max(4, int(self.cache_mb * (1 << 20))
                               // self.block_bytes)
        self.cache_blocks = int(cache_blocks)
        self._stat_lock = threading.Lock()
        self._requests = 0
        self._block_fetches = 0
        self._bytes_fetched = 0
        self._pinned_hits = 0
        if self.policy == "pinned":
            self._pinned = select_pinned_blocks(
                _EdgeBlockIndex(self), self.cache_blocks // 2,
                self.block_bytes,
                entry_bytes=self._dtype["indices"].itemsize)
        else:
            self._pinned = {}
        lru_blocks = self.cache_blocks - len(self._pinned)
        shards = (spec.diskstore.lock_shards if lock_shards is None
                  else int(lock_shards))
        shards = max(1, min(shards, lru_blocks))
        if self.policy == "optimal":
            # Belady victim selection needs one global next-use ordering
            # over the whole budget, so the cache stays unsharded (one
            # lock); the replayed schedule arrives via oracle_feed /
            # oracle_advance
            shards = 1
            self._shards = [OracleCache(lru_blocks)]
        else:
            per = [lru_blocks // shards
                   + (1 if i < lru_blocks % shards else 0)
                   for i in range(shards)]
            self._shards = [LRUCache(max(1, c)) for c in per]
        self._locks = [threading.Lock() for _ in range(shards)]
        self.lock_shards = shards
        self._oracle_replayer = None
        self._oracle_updates: dict[int, tuple] = {}
        self._oracle_lock = threading.Lock()
        io_threads = (spec.diskstore.io_threads if io_threads is None
                      else int(io_threads))
        if io_threads < 1:
            raise ValueError(f"io_threads must be >= 1, got {io_threads}")
        if io_threads > self.lock_shards:
            warnings.warn(
                f"io_threads={io_threads} exceeds lock_shards="
                f"{self.lock_shards}: concurrent preads will serialize on "
                "the page-cache shard locks; raise --lock-shards to match",
                stacklevel=2)
        self.io_threads = io_threads
        self._pool = (ThreadPoolExecutor(max_workers=io_threads,
                                         thread_name_prefix="diskstore-io")
                      if io_threads > 1 else None)
        self._planner_ctx = IOContext()
        self._warmed_nodes = 0
        if self._pinned:
            self._preload_pinned()

    # -- sizes ---------------------------------------------------------------
    @property
    def num_nodes(self) -> int:
        return int(self.manifest["num_nodes"])

    @property
    def num_edges(self) -> int:
        return int(self.manifest["num_edges"])

    @property
    def feat_dim(self) -> int:
        return int(self.manifest["feat_dim"])

    @property
    def n_classes(self) -> int:
        return int(self.manifest.get("n_classes", 0))

    def nbytes_on_disk(self) -> int:
        """Total on-disk footprint: actual (block-padded) file sizes."""
        return sum(os.path.getsize(os.path.join(self.path, a["file"]))
                   for a in self._arrays.values())

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def out_degrees(self, nodes) -> np.ndarray:
        nodes = np.asarray(nodes, np.int64)
        return (self.indptr[nodes + 1] - self.indptr[nodes]).astype(np.int64)

    def edge_byte_range(self, u: int, entry_bytes: int | None = None
                        ) -> tuple[int, int]:
        """Byte extent of node u's neighbor list within ``indices.bin``
        (defaults to the on-disk entry width, int32 = 4 B)."""
        eb = entry_bytes or self._dtype["indices"].itemsize
        return (int(self.indptr[u]) * eb, int(self.indptr[u + 1]) * eb)

    # -- paged read path -----------------------------------------------------
    def _open_backing_files(self, direct_io: bool) -> None:
        """Open one fd per array, preferring ``O_DIRECT`` when asked: the
        kernel page cache then stops double-buffering the store's own
        page cache and every miss is a real device read (the latency the
        ``DirectIOEngine`` cost model stands in for).  Falls back to
        buffered reads — with one warning — when the platform lacks
        O_DIRECT, the block size breaks the 512-byte alignment contract,
        or the filesystem refuses the open/probe read (tmpfs does)."""

        def open_all(extra_flags: int) -> dict:
            return {k: os.open(os.path.join(self.path, a["file"]),
                               os.O_RDONLY | extra_flags)
                    for k, a in self._arrays.items()}

        self.direct_io = False
        reason = None
        if direct_io:
            o_direct = getattr(os, "O_DIRECT", None)
            if o_direct is None:
                reason = "platform has no O_DIRECT"
            elif self.block_bytes % _DIRECT_IO_ALIGN:
                reason = (f"block_bytes={self.block_bytes} is not "
                          f"{_DIRECT_IO_ALIGN}-byte aligned")
            else:
                fds = None
                try:
                    fds = open_all(o_direct)
                    self._fd = fds
                    self.direct_io = True
                    # probe: some filesystems accept the open and then
                    # refuse the first aligned read
                    self._read_block_direct(next(iter(fds)), 0)
                except OSError as e:
                    reason = str(e)
                    self.direct_io = False
                    for fd in (fds or {}).values():
                        os.close(fd)
            if reason is not None:
                warnings.warn(
                    f"direct_io requested but unavailable ({reason}); "
                    "falling back to buffered preads", stacklevel=3)
        if not self.direct_io:
            self._fd = open_all(0)

    def _aligned_buf(self) -> mmap.mmap:
        """Per-thread page-aligned read buffer (mmap pages satisfy any
        logical-block alignment) — O_DIRECT rejects unaligned user
        memory."""
        buf = getattr(self._tls, "dio_buf", None)
        if buf is None:
            buf = mmap.mmap(-1, self.block_bytes)
            self._tls.dio_buf = buf
        return buf

    def _read_block_direct(self, key: str, block: int) -> bytes:
        buf = self._aligned_buf()
        n = os.preadv(self._fd[key], [buf], block * self.block_bytes)
        return buf[:n]

    def _degrade_direct(self, reason: str) -> None:
        """Permanently fall back to buffered preads mid-run (a filesystem
        that accepted the probe may still refuse a later read).  Racing
        reads on the old fds surface as retryable ``io_errors``."""
        with self._stat_lock:
            if not self.direct_io:
                return
            self.direct_io = False
            old = self._fd
            self._fd = {k: os.open(os.path.join(self.path, a["file"]),
                                   os.O_RDONLY)
                        for k, a in self._arrays.items()}
        for fd in old.values():
            os.close(fd)
        warnings.warn(f"direct_io read refused mid-run ({reason}); "
                      "falling back to buffered preads", stacklevel=4)

    def _read_block_raw(self, key: str, block: int) -> bytes:
        if self.direct_io:
            try:
                return self._read_block_direct(key, block)
            except OSError as e:
                import errno
                if e.errno != errno.EINVAL:
                    raise
                self._degrade_direct(str(e))
        return os.pread(self._fd[key], self.block_bytes,
                        block * self.block_bytes)

    def _bill(self, counts: dict) -> None:
        """Add ``preads`` and fault counts to the caller's context and
        the store totals."""
        self._current_ctx().add(**counts)
        with self._stat_lock:
            for k, v in counts.items():
                self._read_totals[k] += v

    def _attempt(self, key: str, block: int, n: int, attempt: int,
                 spans: bool, batch
                 ) -> tuple[bytes | None, str | None, Exception | None]:
        """One ``pread`` of the ``n`` blocks from ``block`` and its checks:
        ``(data, None, None)``, or ``(None, kind, error)`` for an attempt
        that raised OSError (``io_errors``), came back short
        (``short_reads``), failed a block's checksum (``corrupt_blocks``,
        with ``verify``) or ran past ``retry.deadline_s`` a block
        (``timeouts``).  The fault injector and O_DIRECT act on single
        blocks (``n == 1``).  With ``spans`` (``obs.tracing()``, resolved
        once by the caller) the read is a ``disk.pread``/``disk.retry``
        session span for ``batch``."""
        B = self.block_bytes
        t0 = time.perf_counter()
        try:
            with (obs_session.session_span(
                    "disk.pread" if attempt == 0 else "disk.retry",
                    array=key, block=int(block), blocks=n, attempt=attempt,
                    batch=batch) if spans else obs_session.NULL_SPAN):
                if n > 1:
                    data = os.pread(self._fd[key], n * B, block * B)
                elif self._injector is not None:
                    data = self._injector.read(
                        lambda: self._read_block_raw(key, block),
                        key, block, attempt)
                else:
                    data = self._read_block_raw(key, block)
        except OSError as e:
            return None, "io_errors", e
        if len(data) != n * B:
            return None, "short_reads", StoreReadError(
                f"{key} block {block}: short read ({len(data)}/{n * B} "
                "bytes)")
        if self._crc is not None:
            crc = self._crc[key]
            mv = memoryview(data)
            for k in range(n):
                if crc32c(mv[k * B:(k + 1) * B]) != int(crc[block + k]):
                    return None, "corrupt_blocks", StoreReadError(
                        f"{key} block {block + k}: CRC32C mismatch")
        if time.perf_counter() - t0 > self.retry.deadline_s * n:
            return None, "timeouts", StoreReadError(
                f"{key} block {block}: read exceeded the "
                f"{self.retry.deadline_s}s deadline")
        return data, None, None

    def _fetch(self, key: str, block: int) -> bytes:
        """One block read under the retry policy.  Every per-block path
        into disk funnels here — ``_read_range``, the pinned preload, and
        any run the block-batched reads (``_read_runs``) fail to read
        whole — so the policy covers the entire pread surface.  Each
        attempt is one ``_attempt``; failures are retried with
        deterministic-jitter backoff up to ``retry.max_attempts`` total
        tries, then raise ``StoreReadError``.  Fault counters, and the
        ``preads`` of attempts after the first (the caller counts one per
        call), bill the caller's ``IOContext`` (flat keys) plus the store
        totals.
        (The resident ``indptr`` load at open is the one read outside
        this path: it fails loudly at construction, nothing to retry
        into.)"""
        r = self.retry
        faults: dict[str, int] = {}
        last: Exception | None = None
        # pread spans inherit the submitting batch through the IOContext
        # (``_submit`` installs the submitter's ctx on pool threads);
        # resolved once per fetch, only when tracing is on.  They go to
        # the session's tracer alone: in the profiler's trace the read
        # group (``_read_group``) stands for them, since an annotation
        # per block slowed the out-of-core loader by 12.6-15.0% (TPU
        # v5e host)
        spans = obs_session.tracing()
        span_batch = self._current_ctx().batch if spans else None

        def note(kind):
            faults[kind] = faults.get(kind, 0) + 1

        for attempt in range(r.max_attempts):
            data, kind, last = self._attempt(key, block, 1, attempt, spans,
                                             span_batch)
            if kind is None:
                if faults:
                    self._bill(faults)
                return data
            note(kind)
            if attempt + 1 < r.max_attempts:
                note("retries")
                note("preads")
                time.sleep(r.backoff(key, block, attempt))
        self._bill(faults)
        raise StoreReadError(
            f"{key} block {block}: read failed after {r.max_attempts} "
            f"attempt(s): {last}") from last

    # -- I/O attribution -----------------------------------------------------
    def make_io_context(self) -> IOContext:
        """A fresh attribution scope (see ``io_attribution``)."""
        return IOContext()

    def _current_ctx(self) -> IOContext:
        """The attribution context this thread's reads bill to: the one
        installed by ``io_attribution``, else an implicit per-thread
        context (which keeps the one-batch-per-thread deltas of
        ``thread_io_counters`` exact for callers that never install
        one)."""
        ctx = getattr(self._tls, "ctx", None)
        if ctx is None:
            ctx = IOContext()
            self._tls.ctx = ctx
        return ctx

    @contextlib.contextmanager
    def io_attribution(self, ctx: IOContext):
        """Attribute this thread's reads — and any pread-pool work they
        fan out — to ``ctx`` for the duration.  The overlapped loader
        installs one context per minibatch around each stage, so a
        batch's I/O bill is exact even when its stages run on different
        threads and its preads on pool threads."""
        prev = getattr(self._tls, "ctx", None)
        self._tls.ctx = ctx
        try:
            yield ctx
        finally:
            self._tls.ctx = prev

    def _submit(self, fn, *args):
        """Run ``fn`` on the pread pool under the *submitter's*
        attribution context: pool reads issued on behalf of batch t are
        billed to batch t, not to the pool thread."""
        ctx = self._current_ctx()

        def run():
            prev = getattr(self._tls, "ctx", None)
            self._tls.ctx = ctx
            try:
                return fn(*args)
            finally:
                self._tls.ctx = prev

        return self._pool.submit(run)

    def _read_range(self, key: str, lo: int, hi: int) -> bytes:
        """Bytes [lo, hi) of array ``key``, block by block via the cache,
        for a store that does not batch (see the class docstring).  Each
        block locks only its hash shard, so concurrent producers reading
        different blocks proceed in parallel."""
        if hi <= lo:
            return b""
        B = self.block_bytes
        first, last = lo // B, (hi - 1) // B
        ns = self._ns[key] * _NS_STRIDE
        hits = misses = nbytes = evictions = pinned_hits = 0
        parts = []
        for blk in range(first, last + 1):
            bid = ns + blk
            data = self._pinned.get(bid)
            if data is not None:        # immutable after preload: lock-free
                pinned_hits += 1
                parts.append(data)
                continue
            s = bid % self.lock_shards
            shard = self._shards[s]
            lock = self._locks[s]
            with lock:
                data = shard.get(bid)
            if data is None:
                # fetch outside the lock: misses on unrelated blocks that
                # hash to the same shard must not serialize on disk I/O
                payload = self._fetch(key, blk)
                misses += 1
                nbytes += len(payload)
                with lock:
                    # a racing fetch of the same block may have inserted
                    # first; keep its copy (both fetches are counted)
                    data = shard.peek(bid)
                    if data is None:
                        if shard.put(bid, payload) is not None:
                            evictions += 1
                        data = payload
            else:
                hits += 1
            parts.append(data)
        with self._stat_lock:
            self._requests += 1
            self._block_fetches += misses
            self._bytes_fetched += nbytes
            self._pinned_hits += pinned_hits
            self._read_totals["preads"] += misses
        # attribution context: exact per-scope (per-batch) deltas, even
        # when this read runs on a pool thread for another thread's batch
        self._current_ctx().add(
            requests=1, hits=hits + pinned_hits, misses=misses,
            block_fetches=misses, bytes_fetched=nbytes, evictions=evictions,
            preads=misses)
        buf = parts[0] if len(parts) == 1 else b"".join(parts)
        off = lo - first * B
        return buf[off:off + (hi - lo)]

    def _read_blocks(self, key: str, los: np.ndarray, his: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Ranges [los, his) of array ``key`` as one uint8 buffer and each
        range's start in it (empty ranges start at 0).  The buffer holds
        the distinct blocks the ranges cover, ascending
        (``_read_block_list``), so each range is contiguous in it and a
        block two ranges share is looked up once."""
        B = self.block_bytes
        nz = his > los
        first, last = los[nz] // B, (his[nz] - 1) // B
        counts = last - first + 1
        cum = np.cumsum(counts)
        blk = np.unique(np.repeat(first - cum + counts, counts)
                        + np.arange(int(cum[-1]) if cum.size else 0))
        starts = np.zeros(los.size, np.int64)
        starts[nz] = np.searchsorted(blk, first) * B + los[nz] - first * B
        return self._read_block_list(key, blk.tolist(), counts.size), starts

    def _read_block_list(self, key: str, blocks: list, requests: int
                         ) -> np.ndarray:
        """Distinct ascending ``blocks`` of array ``key``, read for
        ``requests`` ranges, as one uint8 buffer filled through the page
        cache:

        1. one locked ``LRUCache.lookup_run`` per shard, which touches the
           blocks in order as one-at-a-time reads would and reserves the
           slots of the misses;
        2. the misses read as contiguous runs, one ``pread`` each
           (``_read_runs``);
        3. the fetched blocks stored in their reserved slots;
        4. one ``IOContext.add`` and one ``_stat_lock`` section.

        Counters match a sequential replay of the blocks."""
        B = self.block_bytes
        buf = np.empty(len(blocks) * B, np.uint8)
        if not blocks:
            return buf
        mv = memoryview(buf)
        ns = self._ns[key] * _NS_STRIDE
        S = self.lock_shards
        pinned = self._pinned
        pinned_hits = 0
        by_shard: dict[int, list[int]] = {}
        for p, b in enumerate(blocks):
            if pinned:          # immutable after preload: lock-free
                data = pinned.get(ns + b)
                if data is not None:
                    mv[p * B:(p + 1) * B] = data
                    pinned_hits += 1
                    continue
            by_shard.setdefault((ns + b) % S, []).append(p)
        hits = evictions = 0
        reserved = []               # (shard, buffer positions to fill)
        for s, pos in by_shard.items():
            with self._locks[s]:
                hit_at, found, miss_at, ev = self._shards[s].lookup_run(
                    [ns + blocks[p] for p in pos])
            evictions += ev
            hits += len(hit_at)
            for k, data in zip(hit_at, found):
                p = pos[k]
                mv[p * B:(p + 1) * B] = data
            if miss_at:
                reserved.append((s, [pos[k] for k in miss_at]))
        miss = [p for _, m in reserved for p in m]
        if len(reserved) > 1:
            miss.sort()
        preads = 0
        try:
            if miss:
                preads, fetched = self._read_runs(key, blocks, miss, mv)
                for s, m in reserved:
                    with self._locks[s]:
                        self._shards[s].fill([ns + blocks[p] for p in m],
                                             [fetched[p] for p in m])
        except BaseException:
            for s, m in reserved:
                with self._locks[s]:
                    self._shards[s].release([ns + blocks[p] for p in m])
            raise
        misses, nbytes = len(miss), len(miss) * B
        with self._stat_lock:
            self._requests += requests
            self._block_fetches += misses
            self._bytes_fetched += nbytes
            self._pinned_hits += pinned_hits
            self._read_totals["preads"] += preads
        self._current_ctx().add(
            requests=requests, hits=hits + pinned_hits, misses=misses,
            block_fetches=misses, bytes_fetched=nbytes, evictions=evictions,
            preads=preads)
        return buf

    def _read_runs(self, key: str, blocks: list, miss: list,
                   mv: memoryview) -> tuple[int, dict]:
        """Read the blocks at ascending buffer positions ``miss`` into
        ``mv``, one ``_attempt`` per run of consecutive blocks.  A run
        that fails is billed as a failed first attempt of a block read
        (its fault kind and one retry) and read again block by block
        through ``_fetch``, under the retry policy.  Returns the
        ``pread``s issued, but for the retries ``_fetch`` bills itself,
        and each position's payload."""
        B = self.block_bytes
        spans = obs_session.tracing()
        batch = self._current_ctx().batch if spans else None
        runs: list[list[int]] = []          # [first position, blocks]
        for p in miss:
            if runs and blocks[p] == blocks[runs[-1][0]] + runs[-1][1]:
                runs[-1][1] += 1
            else:
                runs.append([p, 1])
        fetched: dict[int, bytes] = {}
        preads = len(runs)
        for p, n in runs:
            first = blocks[p]
            data, kind, _ = self._attempt(key, first, n, 0, spans, batch)
            if kind is None:
                mv[p * B:(p + n) * B] = data
                if n == 1:
                    fetched[p] = data
                else:
                    for k in range(n):
                        fetched[p + k] = data[k * B:(k + 1) * B]
                continue
            self._bill({kind: 1, "retries": 1})
            time.sleep(self.retry.backoff(key, first, 0))
            preads += n
            for k in range(n):
                data = self._fetch(key, first + k)
                mv[(p + k) * B:(p + k + 1) * B] = data
                fetched[p + k] = data
        return preads, fetched

    def _read_array(self, key: str, lo_entry: int, hi_entry: int
                    ) -> np.ndarray:
        dt = self._dtype[key]
        lo, hi = lo_entry * dt.itemsize, hi_entry * dt.itemsize
        if not self._batched:
            return np.frombuffer(self._read_range(key, lo, hi), dtype=dt)
        if hi <= lo:
            return np.empty(0, dt)
        B = self.block_bytes
        first = lo // B
        buf = self._read_block_list(
            key, list(range(first, (hi - 1) // B + 1)), 1)
        off = lo - first * B
        return buf[off:off + hi - lo].view(dt)

    def _block_disjoint_groups(self, los: np.ndarray, his: np.ndarray,
                               max_groups: int):
        """Order the byte ranges and split them into <= ``max_groups``
        contiguous runs, cutting only between ranges that do not share a
        disk block — each block is then fetched by exactly one pool
        task, keeping ``block_fetches`` exact (no duplicate racing
        fetches of a shared block) under concurrent reads.  Returns
        index groups into the input arrays, or None when the ranges
        overlap (caller reads serially)."""
        order = np.argsort(los, kind="stable")
        lo_s, hi_s = los[order], his[order]
        if np.any(lo_s[1:] < hi_s[:-1]):
            return None
        B = self.block_bytes
        allowed = np.flatnonzero(lo_s[1:] // B > (hi_s[:-1] - 1) // B) + 1
        k = min(max_groups, allowed.size + 1)
        if k <= 1:
            return [order]
        ideal = np.linspace(0, lo_s.size, k + 1)[1:-1]
        pos = np.unique(allowed[np.minimum(np.searchsorted(allowed, ideal),
                                           allowed.size - 1)])
        return np.split(order, pos)

    def _read_group(self, key: str, los, his, idxs
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Ranges ``idxs`` of array ``key`` as one buffer and each range's
        start in it (``_read_blocks``), in one ``disk.read_group`` span (a
        pool task, or a serial read on the caller's thread) attributed to
        the caller's batch."""
        batch = (self._current_ctx().batch if obs_session.tracing()
                 else None)
        with obs_session.trace_span("disk.read_group", array=key,
                                    ranges=len(idxs), batch=batch):
            if self._batched:
                return self._read_blocks(key, los[idxs], his[idxs])
            parts = [self._read_range(key, int(los[i]), int(his[i]))
                     for i in idxs]
            ends = np.cumsum([len(p) for p in parts], dtype=np.int64)
            return (np.frombuffer(b"".join(parts), np.uint8),
                    ends - (his[idxs] - los[idxs]).clip(0))

    def _read_grouped(self, key: str, los, his, then) -> None:
        """Read many ranges of array ``key`` in groups (``_read_group``)
        and call ``then(idxs, buf, starts)`` for each, on the thread that
        read it, after its read span.  With a pread pool the ranges are
        split at disk-block-clean boundaries and the groups read
        concurrently; all reads stay attributed to the caller's
        context."""
        los = np.asarray(los, np.int64)
        his = np.asarray(his, np.int64)
        n = los.size

        def task(g):
            then(g, *self._read_group(key, los, his, g))

        groups = None
        if self._pool is not None and n >= 2 * self.io_threads:
            groups = self._block_disjoint_groups(los, his, self.io_threads)
        if groups is None or len(groups) <= 1:
            task(np.arange(n))
            return
        for f in [self._submit(task, g) for g in groups]:
            f.result()

    def _read_many(self, key: str, los, his) -> list:
        """Bytes of many ranges of array ``key``, in input order."""
        out: list = [None] * len(los)
        lens = (np.asarray(his, np.int64) - np.asarray(los, np.int64)).clip(0)

        def keep(g, buf, starts):
            for i, s, ln in zip(g.tolist(), starts.tolist(),
                                lens[g].tolist()):
                out[i] = buf[s:s + ln]

        self._read_grouped(key, los, his, keep)
        return out

    def _gather_rows(self, key: str, ids, per_row: int) -> np.ndarray:
        """Rows ``ids`` of array ``key``, ``per_row`` entries each, as an
        ``ids.shape + (per_row,)`` array.  The distinct rows are read in
        groups and cut from each group's buffer in one gather (a row-wide
        item at every byte offset), on the thread that read it."""
        ids = np.asarray(ids)
        flat = ids.reshape(-1)
        uniq, inverse = np.unique(flat, return_inverse=True)
        dt = self._dtype[key]
        width = per_row * dt.itemsize
        row = np.dtype((np.void, width))
        out = np.empty(uniq.size, row)
        los = uniq.astype(np.int64) * width

        def cut(g, buf, starts):
            nonlocal out
            if not g.size:
                return
            got = np.ndarray((buf.size - width + 1,), row, buf, 0,
                             (1,))[starts]
            if g.size == uniq.size:
                out = got
            else:           # ascending rows: a group is a slice of ``out``
                out[int(g[0]):int(g[0]) + g.size] = got

        self._read_grouped(key, los, los + width, cut)
        rows = out.view(dt).reshape(uniq.size, per_row)
        if not np.array_equal(uniq, flat):
            rows = rows[inverse]
        return rows.reshape(ids.shape + (per_row,))

    def _preload_pinned(self) -> None:
        """Load the pinned hot blocks' payloads eagerly (the §IV-C runtime
        stages its scratchpad before training starts).  The staging reads
        count as block fetches — they are real disk I/O.  After this the
        pinned dict is never mutated, which is what makes the lock-free
        reads (``_read_range``, ``_read_blocks``) safe."""
        ns = self._ns["indices"] * _NS_STRIDE
        for blk in sorted(self._pinned):
            data = self._fetch("indices", blk - ns)
            self._pinned[blk] = data
            self._block_fetches += 1
            self._bytes_fetched += len(data)
            self._read_totals["preads"] += 1

    # -- GraphStore access methods -------------------------------------------
    def neighbors(self, u: int) -> np.ndarray:
        return self._read_array("indices", int(self.indptr[u]),
                                int(self.indptr[u + 1]))

    def gather_edges(self, rows, offsets) -> np.ndarray:
        """Same contract as ``CSRGraph.gather_edges`` — but each row's
        neighbor-list chunk is fetched through the page cache, so the
        block-request stream is exactly the per-target "chunk" fetch the
        paper's storage tier serves."""
        rows = np.asarray(rows, np.int64)
        off = np.asarray(offsets, np.int64)
        out = np.empty(off.shape, np.int32)
        ip = self.indptr
        if self._pool is not None and rows.size >= 2 * self.io_threads:
            # pooled path: one deduplicated neighbor-list read per
            # distinct row, fanned out over the pread pool (``requests``
            # then counts deduped list reads, not per-occurrence touches)
            dt = self._dtype["indices"]
            uniq, inverse = np.unique(rows, return_inverse=True)
            lo = ip[uniq] * dt.itemsize
            hi = ip[uniq + 1] * dt.itemsize
            nz = np.flatnonzero(hi > lo)
            bufs = self._read_many("indices", lo[nz], hi[nz])
            lists: dict[int, np.ndarray] = {
                int(j): np.frombuffer(raw, dtype=dt)
                for j, raw in zip(nz, bufs)}
            for i, u in enumerate(inverse):
                lst = lists.get(int(u))
                out[i] = lst[off[i]] if lst is not None else rows[i]
            return out
        for i, u in enumerate(rows):
            lo, hi = int(ip[u]), int(ip[u + 1])
            if hi > lo:
                out[i] = self._read_array("indices", lo, hi)[off[i]]
            else:
                out[i] = u
        return out

    def gather_features(self, ids) -> np.ndarray:
        if "features" not in self._arrays:
            raise ValueError(f"{self.path}: store has no feature table")
        return self._gather_rows("features", ids, self.feat_dim)

    def gather_labels(self, ids) -> np.ndarray:
        if "labels" not in self._arrays:
            raise ValueError(f"{self.path}: store has no labels")
        ids = np.asarray(ids)
        return self._gather_rows("labels", ids, 1).reshape(ids.shape)

    def gather_edge_blocks(self, blocks, block_e: int) -> np.ndarray:
        """``block_e``-wide int32 chunks of ``indices``, zero-padded past
        the array end — read through the page cache, so device edge-block
        cache misses are real paged disk I/O and land in the counters."""
        from repro.core.graph import read_edge_blocks
        blocks_a = np.asarray(blocks, np.int64).reshape(-1)
        read = lambda lo, hi: self._read_array("indices", lo, hi)  # noqa: E731
        if self._pool is not None and blocks_a.size >= 2 * self.io_threads:
            # pre-fetch the distinct blocks' ranges concurrently, then
            # let the shared slicer assemble from the staged buffers
            E = self.num_edges
            dt = self._dtype["indices"]
            uniq = np.unique(blocks_a)
            lo_e = uniq * block_e
            hi_e = np.minimum(lo_e + block_e, E)
            nz = np.flatnonzero(hi_e > lo_e)
            bufs = self._read_many("indices", lo_e[nz] * dt.itemsize,
                                   hi_e[nz] * dt.itemsize)
            served = {(int(lo_e[j]), int(hi_e[j])):
                      np.frombuffer(raw, dtype=dt)
                      for j, raw in zip(nz, bufs)}
            fallback = read
            read = lambda lo, hi: (served.get((lo, hi))  # noqa: E731
                                   if (lo, hi) in served
                                   else fallback(lo, hi))
        return read_edge_blocks(read, blocks_a, block_e, self.num_edges)

    # -- planner hook --------------------------------------------------------
    def warm_nodes(self, nodes, *, features: bool = True,
                   edges: bool = True) -> int:
        """Planner pre-admission: asynchronously pull the given nodes'
        neighbor-list and feature-row byte ranges through the page cache
        on the pread pool, ahead of the batch that will read them.
        Fire-and-forget — payloads are dropped; the value is the cache
        residency when the real read arrives.  Billed to the store's
        dedicated planner context (``stats()['planner']``), never to a
        batch.  Returns the number of ranges submitted (0 without a
        pool: synchronous warming would just move the stall)."""
        if self._pool is None:
            return 0
        nodes = np.unique(np.asarray(nodes, np.int64).reshape(-1))
        if nodes.size == 0:
            return 0
        jobs = []
        if edges:
            isz = self._dtype["indices"].itemsize
            lo = self.indptr[nodes] * isz
            hi = self.indptr[nodes + 1] * isz
            nz = hi > lo
            jobs.append(("indices", lo[nz], hi[nz]))
        if features and "features" in self._arrays:
            row = self._dtype["features"].itemsize * self.feat_dim
            lo = nodes * row
            jobs.append(("features", lo, lo + row))
        n = 0
        prev = getattr(self._tls, "ctx", None)
        self._tls.ctx = self._planner_ctx     # bind submissions to planner
        try:
            for key, lo, hi in jobs:
                if lo.size == 0:
                    continue
                groups = self._block_disjoint_groups(
                    np.asarray(lo, np.int64), np.asarray(hi, np.int64),
                    self.io_threads)
                if groups is None:
                    continue
                for g in groups:
                    self._submit(self._read_group, key, lo, hi, g)
                    n += len(g)
        finally:
            self._tls.ctx = prev
        with self._stat_lock:
            self._warmed_nodes += int(nodes.size)
        return n

    # -- oracle (Belady) scheduling hooks ------------------------------------
    def read_indices_at(self, positions) -> np.ndarray:
        """Raw positional reads of ``indices[positions]`` for sampler
        replay: direct (retry-protected) block preads that bypass the
        page cache entirely — no residency changes, no request/hit/miss
        accounting (its ``preads`` and faults are billed).  The oracle
        replayer must observe the same bytes training will read *without*
        perturbing the cache it is scheduling."""
        dt = self._dtype["indices"]
        per = self.block_bytes // dt.itemsize
        pos = np.asarray(positions, np.int64).reshape(-1)
        uniq, inv = np.unique(pos, return_inverse=True)
        out = np.empty(uniq.size, dt)
        blocks = uniq // per
        distinct = np.unique(blocks)
        for b in distinct:
            sel = blocks == b
            data = np.frombuffer(self._fetch("indices", int(b)), dtype=dt)
            out[sel] = data[uniq[sel] - int(b) * per]
        self._bill({"preads": int(distinct.size)})
        return out[inv].reshape(np.shape(positions))

    def replay_block_ids(self, *, feature_nodes=None, edge_nodes=None,
                         label_nodes=None, edge_blocks=None,
                         block_e: int | None = None) -> np.ndarray:
        """Namespaced page-cache block ids a replayed batch's reads will
        touch: feature rows of ``feature_nodes``, neighbor lists of
        ``edge_nodes``, label entries of ``label_nodes``, and/or
        ``block_e``-entry edge blocks (the device edge-cache fetch
        granularity).  Pure layout arithmetic over the resident
        ``indptr`` — no disk reads."""
        B = self.block_bytes
        parts: list[np.ndarray] = []

        def ranges_to_blocks(key, lo, hi):
            ns = self._ns[key] * _NS_STRIDE
            lo = np.asarray(lo, np.int64).reshape(-1)
            hi = np.asarray(hi, np.int64).reshape(-1)
            keep = hi > lo
            lo, hi = lo[keep], hi[keep]
            if lo.size == 0:
                return
            first = lo // B
            counts = (hi - 1) // B - first + 1
            total = int(counts.sum())
            starts = np.repeat(first, counts)
            offs = (np.arange(total)
                    - np.repeat(np.cumsum(counts) - counts, counts))
            parts.append(ns + starts + offs)

        if feature_nodes is not None and "features" in self._arrays:
            row = self._dtype["features"].itemsize * self.feat_dim
            ids = np.asarray(feature_nodes, np.int64).reshape(-1)
            ranges_to_blocks("features", ids * row, ids * row + row)
        if edge_nodes is not None:
            isz = self._dtype["indices"].itemsize
            ids = np.asarray(edge_nodes, np.int64).reshape(-1)
            ranges_to_blocks("indices", self.indptr[ids] * isz,
                             self.indptr[ids + 1] * isz)
        if edge_blocks is not None:
            isz = self._dtype["indices"].itemsize
            eb = np.asarray(edge_blocks, np.int64).reshape(-1)
            lo_e = eb * int(block_e)
            hi_e = np.minimum(lo_e + int(block_e), self.num_edges)
            ranges_to_blocks("indices", lo_e * isz, hi_e * isz)
        if label_nodes is not None and "labels" in self._arrays:
            isz = self._dtype["labels"].itemsize
            ids = np.asarray(label_nodes, np.int64).reshape(-1)
            ranges_to_blocks("labels", ids * isz, ids * isz + isz)
        if not parts:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(parts))

    def oracle_attach(self, replayer) -> None:
        """Bind the replay lane that keeps this store's Belady schedule
        one window ahead (``storage.oracle.OracleReplayer``).  Only
        meaningful — and only allowed — under ``policy='optimal'``."""
        if self.policy != "optimal":
            raise ValueError(
                f"oracle_attach on a {self.policy!r}-policy store; the "
                "replayed schedule only drives policy='optimal'")
        self._oracle_replayer = replayer

    def oracle_feed(self, updates: dict) -> None:
        """Accept per-batch next-use updates from the replay lane:
        ``{batch_idx: (block_ids, next_use)}`` in this store's namespaced
        block space."""
        with self._oracle_lock:
            self._oracle_updates.update(updates)

    def oracle_advance(self, idx: int) -> None:
        """Enter batch ``idx``: make sure its window's schedule has been
        replayed (blocking only when the replay lane is behind) and apply
        the batch's next-use times to the page cache.  No-op for
        non-optimal policies and for batches with no schedule (the cache
        then degrades toward FIFO — quality only, reads stay exact)."""
        if self.policy != "optimal":
            return
        rep = self._oracle_replayer
        if rep is not None:
            rep.advance(idx)
        with self._oracle_lock:
            upd = self._oracle_updates.pop(idx, None)
        if upd is None:
            return
        bids, nu = upd
        with self._locks[0]:
            self._shards[0].begin_batch(idx, bids, nu)

    # -- accounting ----------------------------------------------------------
    def io_counters(self) -> dict:
        hits = misses = evictions = 0
        for shard, lock in zip(self._shards, self._locks):
            with lock:      # per-shard-consistent vs. in-flight reads
                hits += shard.hits
                misses += shard.misses
                evictions += shard.evictions
        with self._stat_lock:
            return {"requests": self._requests,
                    "block_fetches": self._block_fetches,
                    "bytes_fetched": self._bytes_fetched,
                    "hits": hits + self._pinned_hits, "misses": misses,
                    "evictions": evictions, **self._read_totals}

    def thread_io_counters(self) -> dict:
        """This thread's attribution scope: the installed ``IOContext``
        (``io_attribution``), else the implicit per-thread context.
        Either way, deltas of this view give exact per-batch attribution
        even with concurrent producers *and* pool preads — work the pool
        runs on this scope's behalf is billed here, not to the pool
        thread (the global ``io_counters`` stay the cross-thread
        totals)."""
        return self._current_ctx().counters()

    def stats(self) -> dict:
        return {"kind": self.kind, "policy": self.policy,
                "cache_mb": self.cache_mb,
                "cache_blocks": self.cache_blocks,
                "lock_shards": self.lock_shards,
                "io_threads": self.io_threads,
                "verify": self.verify,
                "direct_io": self.direct_io,
                "nbytes_on_disk": self.nbytes_on_disk(),
                "planner": dict(self._planner_ctx.counters(),
                                warmed_nodes=self._warmed_nodes),
                **self.io_counters()}

    def to_csr(self, include_features: bool = True) -> CSRGraph:
        """Materialize the graph in memory (device backends and tests;
        defeats the point for the out-of-core host path).  With
        ``include_features=False`` the (usually dominant) feature table is
        left on disk — the right call when a device feature-cache tier
        will fetch rows on demand anyway."""
        read = {k: np.fromfile(os.path.join(self.path, a["file"]),
                               dtype=self._dtype[k],
                               count=int(np.prod(a["shape"])))
                for k, a in self._arrays.items()
                if include_features or k != "features"}
        feats = read.get("features")
        if feats is not None:
            feats = feats.reshape(self._arrays["features"]["shape"])
        return CSRGraph(indptr=read["indptr"].astype(np.int64),
                        indices=read["indices"].astype(np.int32),
                        features=feats, labels=read.get("labels"),
                        name=self.name)

    def close(self) -> None:
        if self._oracle_replayer is not None:
            self._oracle_replayer.close()
            self._oracle_replayer = None
        if self._pool is not None:
            # drain before the fds go away: in-flight warms/gathers hold
            # open descriptors, and cancel whatever hasn't started
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None
        for fd in self._fd.values():
            os.close(fd)
        self._fd = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _EdgeBlockIndex:
    """Adapter giving ``PinnedCache`` the degree-heat + byte-range view of
    the on-disk edge-list array, in the store's namespaced block space."""

    def __init__(self, store: DiskStore):
        self._store = store
        self._base = store._ns["indices"] * _NS_STRIDE * store.block_bytes

    def degrees(self) -> np.ndarray:
        return self._store.degrees()

    def edge_byte_range(self, u: int, entry_bytes: int) -> tuple[int, int]:
        lo, hi = self._store.edge_byte_range(u, entry_bytes)
        return (self._base + lo, self._base + hi)


def open_store(kind: str, *, g: CSRGraph | None = None,
               path: str | None = None, block_bytes: int | None = None,
               **kw) -> GraphStore:
    """``mem`` needs ``g``; ``disk`` needs ``path`` (saving ``g`` there
    first when given, laid out in ``block_bytes`` units; an existing
    layout keeps its own block size)."""
    if kind == "mem":
        if g is None:
            raise ValueError("mem store needs a graph")
        return InMemoryStore(g)
    if kind == "disk":
        if path is None:
            raise ValueError("disk store needs a path")
        if g is not None and not os.path.exists(os.path.join(path, MANIFEST)):
            save_graph(g, path, block_bytes=block_bytes)
        store = DiskStore(path, **kw)
        if g is not None:
            # a pre-existing layout is reused only if it holds this graph
            # — silently serving a stale one would train the wrong data
            if (store.name, store.num_nodes, store.num_edges,
                    store.feat_dim) != (g.name, g.num_nodes, g.num_edges,
                                        g.feat_dim):
                store.close()
                raise ValueError(
                    f"{path} holds graph {store.name!r} "
                    f"({store.num_nodes} nodes, {store.num_edges} edges), "
                    f"not {g.name!r} ({g.num_nodes} nodes, "
                    f"{g.num_edges} edges); point --store-dir elsewhere "
                    "or remove the stale layout")
        return store
    raise KeyError(f"unknown graph store {kind!r}; have ('mem', 'disk')")
