"""Unified minibatch data plane: one ``SubgraphLoader`` interface over the
host, ISP-mesh, and Pallas data-preparation backends.

The paper's argument is a comparison of *data-preparation backends* feeding
the same GraphSAGE consumer (in-memory vs. mmap-SSD vs. ISP).  This module
is that seam: every backend produces the same ``Minibatch`` (per-hop IDs,
per-hop features, labels, optional storage ``SampleTrace``), so the trainer,
benchmarks, and storage simulator compose with any of them.

Backends (``make_loader(name, ...)``):

* ``host``   — numpy ``sample_khop`` + feature indexing through a
  ``GraphStore`` (in-memory arrays, or real paged disk reads via
  ``storage.store.DiskStore`` — the out-of-core path), wrapped in the
  ``ProducerConsumerPipeline`` for async production (the paper's CPU
  data-preparation stage, Fig. 4).
* ``isp``    — the ``ISPGraph`` shard_map path: each mesh shard samples the
  targets it owns and only the dense subgraph crosses the links (the ISP
  architecture).
* ``pallas`` — composes the ``kernels/neighbor_sample`` k-hop with the
  ``kernels/feature_gather`` row gather: the single-device in-storage-style
  kernel path (HBM as flash, VMEM as the SSD page buffer).  With
  ``device_cache`` set, feature rows read through an HBM-resident
  ``storage.devcache.DeviceFeatureCache`` instead of a full-table upload
  (the device-side out-of-core path, bit-identical at equal seeds).

The host backend additionally supports ``sampler='saint'`` (GraphSAINT
random walks) next to the default ``'khop'`` fanout expansion.

A simulated storage tier (``storage/engines.py``) can be attached to any
loader: each batch's access trace is replayed against the engine's cost
model and the resulting latency is imposed on production
(``produce_delay_s`` of the pipeline), connecting the performance simulator
to live training.

Any backend can additionally be wrapped in asynchronous prefetch
(``make_loader(..., prefetch=N)`` -> ``pipeline.PrefetchingLoader``): a
background worker produces batch ``i+1`` — device dispatch and the
simulated-storage trace included — while the consumer trains on batch
``i``, with bit-identical results to the synchronous path.

Randomness contract: targets for batch ``i`` come from
``np.random.default_rng(seed + i)``; device backends draw sampling
randomness from ``jax.random.fold_in(jax.random.key(seed), i)`` with one
further per-hop fold — identical between the ``isp`` and ``pallas``
backends, so their sampled IDs match exactly.  The host backend uses the
numpy reference sampler (same distribution, different stream), so only
shapes are guaranteed to match it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import warnings
from typing import Protocol, Sequence, runtime_checkable

import numpy as np

from repro import obs
from repro.core.config import (BackendSpec, CacheTierSpec, PipelineSpec,
                               PrefetchSpec, SamplerSpec, StoreSpec)
from repro.core.graph import CSRGraph
from repro.core.sampler import (DEFAULT_FANOUTS, SampleTrace, _io_delta,
                                _io_snapshot, sample_khop, saint_random_walk)
from repro.obs.metrics import idle_fraction as _idle_fraction
from repro.storage.store import StoreReadError, nest_fault_counters


@dataclasses.dataclass
class Minibatch:
    """One training minibatch, backend-agnostic.

    targets:   (M,) int32 — the batch's seed nodes.
    hop_ids:   hop_ids[t] has shape (M, f1, ..., ft) — sampled node IDs.
    hop_feats: hop_feats[t] has shape (M, f1, ..., ft, F) — their features.
    labels:    (M,) int32.
    trace:     the storage access trace (host backend only; the unit the
               storage simulator replays).
    """

    targets: object
    hop_ids: list
    hop_feats: list
    labels: object
    trace: SampleTrace | None = None

    @property
    def batch_size(self) -> int:
        return int(np.asarray(self.targets).shape[0])

    @property
    def depth(self) -> int:
        return len(self.hop_ids) - 1


@runtime_checkable
class SubgraphLoader(Protocol):
    """The data-preparation stage: batch index -> Minibatch."""

    backend: str
    fanouts: tuple[int, ...]

    def get_batch(self, idx: int) -> Minibatch: ...

    def stats(self) -> dict: ...

    def close(self) -> None: ...


LOADERS: dict[str, type] = {}


def register_loader(name: str):
    def deco(cls):
        cls.backend = name
        LOADERS[name] = cls
        return cls
    return deco


def make_loader(name: str, g: CSRGraph | None, *, batch_size: int = 64,
                fanouts: Sequence[int] = DEFAULT_FANOUTS, mesh=None,
                seed: int = 0, storage_engine=None, prefetch: int = 0,
                store=None, sampler: str = "khop", walk_length: int = 4,
                device_cache=None, **kw) -> "SubgraphLoader":
    """DEPRECATED keyword-soup shim over the declarative spec API.

    New call sites should build a ``core.config.PipelineSpec`` and call
    ``core.config.build_pipeline(spec, graph_or_store)``; this shim
    assembles exactly that spec from its keyword arguments (so the two
    paths share one construction and one validation layer — training is
    bit-identical between them, asserted in tests/test_config.py) and
    returns the bare loader.

    ``device_cache`` (a ``storage.specs.DeviceCacheSpec``, pallas backend
    only) becomes a device ``CacheTierSpec`` over the feature rows;
    ``store`` stays a live object — the spec records only its kind.
    Host-pipeline knobs (``n_workers``/``queue_depth``/
    ``straggler_factor``) and the isp ``axis`` ride in ``**kw``.
    """
    if name not in LOADERS:
        raise KeyError(f"unknown backend {name!r}; have {sorted(LOADERS)}")
    backend_kw = {k: kw.pop(k) for k in ("n_workers", "queue_depth",
                                         "straggler_factor", "axis")
                  if k in kw}
    if kw:
        raise TypeError(f"make_loader got unknown kwargs {sorted(kw)}")
    tiers = []
    if device_cache is not None and (
            getattr(device_cache, "rows", 0)
            or getattr(device_cache, "edge_blocks", 0)):
        tiers.append(CacheTierSpec.device(
            rows=getattr(device_cache, "rows", 0),
            edge_blocks=getattr(device_cache, "edge_blocks", 0),
            policy=device_cache.policy,
            pinned_fraction=device_cache.pinned_fraction,
            oracle_window=getattr(device_cache, "oracle_window", 0)))
    spec = PipelineSpec(
        backend=BackendSpec(name=name, **backend_kw),
        sampler=SamplerSpec(family=sampler, fanouts=tuple(fanouts),
                            walk_length=walk_length),
        store=StoreSpec(kind=getattr(store, "kind", "mem")),
        cache_tiers=tuple(tiers),
        prefetch=PrefetchSpec(depth=prefetch),
        batch_size=batch_size, seed=seed)
    return _build_loader(spec, g=g, store=store, mesh=mesh,
                         storage_engine=storage_engine)


def _build_loader(spec: PipelineSpec, *, g: CSRGraph | None, store=None,
                  mesh=None, storage_engine=None) -> "SubgraphLoader":
    """Construct the backend loader a validated spec describes.

    Shared by ``config.build_pipeline`` (which also materializes the
    store/engine the spec asks for) and the ``make_loader`` shim (whose
    callers pass live objects).  ``store`` selects where graph data is
    *read from*; the device backends materialize a ``CSRGraph`` from it
    only when ``g`` is not given — with a loud warning, since that loads
    the whole store into DRAM, and skipping the feature table when a
    device feature-cache tier will fetch rows on demand anyway.
    """
    name = spec.backend.name
    if name not in LOADERS:
        raise KeyError(f"unknown backend {name!r}; have {sorted(LOADERS)}")
    feature_cache = spec.feature_cache()
    edge_cache = spec.topology_cache()
    if g is None and store is not None and name != "host":
        skip_features = feature_cache is not None
        nbytes = getattr(store, "nbytes_on_disk", lambda: 0)()
        warnings.warn(
            f"materializing the full graph from the {store.kind!r} store "
            f"into DRAM for the {name!r} backend"
            + (f" (~{nbytes / 2**20:.0f} MB on disk"
               + (", feature table left on disk for the device cache)"
                  if skip_features else ")") if nbytes else "")
            + "; pass the CSRGraph directly, or use the host backend, to "
              "avoid the copy", stacklevel=3)
        import inspect
        params = inspect.signature(store.to_csr).parameters
        if "include_features" in params:
            g = store.to_csr(include_features=not skip_features)
        else:                           # stores predating the parameter
            g = store.to_csr()
    kw = {}
    if name == "host":
        kw.update(n_workers=spec.backend.n_workers,
                  queue_depth=spec.backend.queue_depth,
                  straggler_factor=spec.backend.straggler_factor)
    elif name == "isp":
        kw.update(axis=spec.backend.axis)
    elif name == "pallas":
        kw.update(device_cache=feature_cache, edge_cache=edge_cache)
    loader = LOADERS[name](g, batch_size=spec.batch_size,
                           fanouts=spec.sampler.fanouts, mesh=mesh,
                           seed=spec.seed, sampler=spec.sampler.family,
                           walk_length=spec.sampler.walk_length,
                           storage_engine=storage_engine, store=store, **kw)
    if any(t.policy == "optimal" for t in spec.cache_tiers):
        from repro.storage.oracle import (attach_host_oracle,
                                          attach_pallas_oracle)
        if name == "pallas":
            attach_pallas_oracle(loader, spec)
        elif name == "host":
            attach_host_oracle(loader, spec)
    if spec.prefetch.depth:
        if spec.prefetch.overlap:
            from repro.core.pipeline import OverlappedLoader
            plan_ahead = _effective_plan_ahead(
                spec.prefetch.plan_ahead, store, spec.batch_size)
            faults = getattr(spec.store, "faults", None)
            loader = OverlappedLoader(
                loader, depth=spec.prefetch.depth,
                stage_depth=spec.prefetch.stage_depth,
                plan_ahead=plan_ahead,
                lane_timeout=spec.prefetch.lane_timeout_s,
                max_lane_restarts=spec.prefetch.max_lane_restarts,
                stall_inject=(faults.lane_stall
                              if faults is not None else None))
        else:
            from repro.core.pipeline import PrefetchingLoader
            loader = PrefetchingLoader(loader, depth=spec.prefetch.depth)
    return loader


def _effective_plan_ahead(plan_ahead: int, store, batch_size: int) -> int:
    """Frontier-planner guard: warming ``plan_ahead`` future batches only
    helps while the page cache can hold the planned window's working set
    alongside the current batch.  When it cannot, the warmed blocks evict
    each other (and the live batch's blocks) before they are consumed —
    a measured slowdown — so the planner is disabled with a one-time
    warning instead of letting the config footgun fire."""
    if not plan_ahead or store is None or not hasattr(store, "cache_blocks"):
        return plan_ahead
    try:
        bb = store.block_bytes
        row = store._dtype["features"].itemsize * store.feat_dim
        esz = store._dtype["indices"].itemsize
        avg_deg = store.num_edges / max(1, store.num_nodes)
        per_target = (max(1, -(-row // bb))            # feature row blocks
                      + max(1, int(avg_deg * esz // bb) + 1))  # edge list
        working_set = (plan_ahead + 1) * batch_size * per_target
    except (AttributeError, KeyError, TypeError):
        return plan_ahead
    if store.cache_blocks >= working_set:
        return plan_ahead
    warnings.warn(
        f"plan_ahead={plan_ahead} disabled: the page cache holds "
        f"{store.cache_blocks} blocks but the planned window's working "
        f"set is ~{working_set} blocks ({plan_ahead + 1} batches x "
        f"{batch_size} targets); warming would thrash the cache it is "
        "trying to fill — grow cache_mb or lower plan_ahead to re-enable",
        stacklevel=3)
    return 0


def batch_targets(g, idx: int, batch_size: int,
                  seed: int = 0) -> np.ndarray:
    """The shared per-batch target stream (pure function of the index).
    ``g`` is anything with ``num_nodes`` — a CSRGraph or a GraphStore —
    so mem- and disk-backed runs draw identical targets."""
    rng = np.random.default_rng(seed + idx)
    return rng.integers(0, g.num_nodes, batch_size).astype(np.int32)


class _LoaderBase:
    """Shared target generation + simulated-storage accounting."""

    backend = "base"
    SAMPLERS = ("khop",)

    def __init__(self, g: CSRGraph | None, *, batch_size: int, fanouts,
                 seed: int = 0, storage_engine=None, store=None,
                 sampler: str = "khop", walk_length: int = 4):
        self.g = g
        self.store = store if store is not None else g
        if self.store is None:
            raise ValueError("loader needs a graph or a GraphStore")
        if sampler not in self.SAMPLERS:
            raise ValueError(
                f"backend {self.backend!r} supports samplers "
                f"{self.SAMPLERS}, not {sampler!r} (GraphSAINT walks are "
                "host-side numpy sampling)")
        self.sampler = sampler
        self.walk_length = int(walk_length)
        self.batch_size = batch_size
        # a SAINT batch's one hop tensor is the (M, L+1) walk — report the
        # matching fanout so the GNN shape contract still holds
        self.fanouts = ((self.walk_length + 1,) if sampler == "saint"
                        else tuple(fanouts))
        self.seed = seed
        self.storage_engine = storage_engine
        self.simulated_storage_s = 0.0
        self._storage_lock = threading.Lock()
        self.devcache = None
        self.edgecache = None
        self._epoch0 = None
        self._oracle = None        # OracleReplayer (optimal-policy tiers)

    def targets(self, idx: int) -> np.ndarray:
        return batch_targets(self.store, idx, self.batch_size, self.seed)

    def _advance_oracle(self, idx: int) -> None:
        """Head-of-batch hook for optimal-policy (Belady) tiers: make
        sure the replay lane has batch ``idx``'s window scheduled, then
        roll each scheduled cache's two-phase next-use state forward.
        All three calls are no-ops for lru/pinned configurations."""
        rep = self._oracle
        if rep is not None:
            rep.advance(idx)
        ec = self.edgecache
        if ec is not None:
            ec.oracle_begin_batch(idx)
        adv = getattr(self.store, "oracle_advance", None)
        if adv is not None:
            adv(idx)

    def storage_delay(self, trace: SampleTrace) -> float:
        """Replay ``trace`` against the attached engine's cost model and
        return the simulated data-preparation latency (0 if no engine).
        Called from producer threads, so the accounting is locked; a
        straggler-reissued batch pays (and records) its cost twice, like
        the duplicated work it models."""
        if self.storage_engine is None or trace is None:
            return 0.0
        eng = self.storage_engine
        delay = eng.batch_cost(trace).time_s + eng.feature_time(trace)
        with self._storage_lock:
            self.simulated_storage_s += delay
        return delay

    def storage_cost_trace(self, idx: int) -> SampleTrace:
        """The cost-model access trace for device backends, which have no
        host trace: a numpy re-sample with the same algorithmic event
        counts (host RNG stream)."""
        g = self.g if self.g is not None else self.store
        if self.sampler == "saint":
            return saint_random_walk(g, self.targets(idx), self.walk_length,
                                     seed=self.seed + idx)
        return sample_khop(g, self.targets(idx), self.fanouts,
                           seed=self.seed + idx)

    def impose_storage_cost(self, idx: int) -> None:
        """Replay batch ``idx``'s cost-model trace against the attached
        engine and impose the simulated latency.  The numpy re-sample's
        real cost is deducted from the sleep, so the visible delay stays
        equal to the *modeled* latency and the backend comparison is not
        skewed by cost-model overhead.  This runs inside ``get_batch``, so
        under a ``PrefetchingLoader`` both the re-sample and the sleep
        happen in the prefetch worker — off the consumer's critical path."""
        if self.storage_engine is None:
            return
        t0 = time.perf_counter()
        delay = self.storage_delay(self.storage_cost_trace(idx))
        time.sleep(max(0.0, delay - (time.perf_counter() - t0)))

    def _counter_sources(self) -> dict:
        src = {}
        io = getattr(self.store, "io_counters", None)
        if io is not None:
            src["store"] = io
        if self.devcache is not None:
            src["devcache"] = self.devcache.counters
        if self.edgecache is not None:
            src["edgecache"] = self.edgecache.counters
        return src

    def start_epoch(self) -> None:
        """Mark an epoch boundary: from here on, ``stats()`` reports the
        cache counters *per-epoch* (``store_epoch`` / ``devcache_epoch``
        deltas since this call) alongside the cumulative totals, so
        hit-rate curves are comparable across epochs instead of being
        swamped by warmup/preload traffic."""
        self._epoch0 = {k: fn() for k, fn in self._counter_sources().items()}

    def stats(self) -> dict:
        s = {"backend": self.backend, "sampler": self.sampler,
             "simulated_storage_s": self.simulated_storage_s}
        store_stats = getattr(self.store, "stats", None)
        if store_stats is not None:
            s["store"] = store_stats()
        if self.devcache is not None:
            s["devcache"] = self.devcache.stats()
        if self.edgecache is not None:
            s["edgecache"] = self.edgecache.stats()
        if self._oracle is not None:
            s["oracle"] = self._oracle.stats()
        if self._epoch0 is not None:
            for name, fn in self._counter_sources().items():
                base = self._epoch0.get(name, {})
                s[f"{name}_epoch"] = {
                    k: v - base.get(k, 0) for k, v in fn().items()
                    if isinstance(v, (int, float))}
        return s

    def close(self) -> None:
        if self._oracle is not None:
            self._oracle.close()


# ---------------------------------------------------------------------------
# host backend — numpy sampler + async producer pipeline
# ---------------------------------------------------------------------------

@register_loader("host")
class HostSubgraphLoader(_LoaderBase):
    """CPU data preparation (paper Fig. 4): ``sample_khop`` (or GraphSAINT
    random walks, ``sampler='saint'``) + feature indexing in producer
    threads, consumed strictly in batch order.  All graph reads go
    through ``self.store`` — in-memory arrays by default, real paged disk
    reads when a ``DiskStore`` is attached (the out-of-core path).  The
    storage engine's per-trace cost is imposed inside ``produce`` so the
    pipeline's idle-fraction metric reflects the simulated tier."""

    SAMPLERS = ("khop", "saint")

    def __init__(self, g, *, batch_size, fanouts, mesh=None, seed=0,
                 storage_engine=None, store=None, sampler="khop",
                 walk_length=4, n_workers: int = 4,
                 queue_depth: int = 8, straggler_factor: float = 4.0):
        super().__init__(g, batch_size=batch_size, fanouts=fanouts,
                         seed=seed, storage_engine=storage_engine,
                         store=store, sampler=sampler,
                         walk_length=walk_length)
        from repro.core.pipeline import (ProducerConsumerPipeline,
                                         make_host_producer)
        produce = make_host_producer(self.store, batch_size, self.fanouts,
                                     seed=seed, sampler=self.sampler,
                                     walk_length=self.walk_length,
                                     storage_cost_fn=self.storage_delay)
        self.pipeline = ProducerConsumerPipeline(
            produce, n_workers=n_workers, queue_depth=queue_depth,
            straggler_factor=straggler_factor)

    def get_batch(self, idx: int) -> Minibatch:
        return self.pipeline.get_batch(idx)

    def stats(self) -> dict:
        s = self.pipeline.stats
        produce = s.produce_times
        return dict(super().stats(),
                    mean_produce_s=float(np.mean(produce)) if produce else 0.0,
                    reissued=s.reissued,
                    duplicates_dropped=s.duplicates_dropped)

    def close(self) -> None:
        self.pipeline.close()
        super().close()


# ---------------------------------------------------------------------------
# isp backend — near-data sampling on the mesh
# ---------------------------------------------------------------------------

@register_loader("isp")
class ISPSubgraphLoader(_LoaderBase):
    """Near-data (ISP) data preparation: the partitioned graph lives sharded
    on the mesh; sampling + gathering run where the shard lives and only the
    dense subgraph crosses the links."""

    def __init__(self, g, *, batch_size, fanouts, mesh=None, seed=0,
                 storage_engine=None, store=None, sampler="khop",
                 walk_length=4, axis: str = "data"):
        super().__init__(g, batch_size=batch_size, fanouts=fanouts,
                         seed=seed, storage_engine=storage_engine,
                         store=store, sampler=sampler,
                         walk_length=walk_length)
        import jax
        import jax.numpy as jnp
        from repro.core.isp import ISPGraph
        from repro.core.partition import partition_graph
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.mesh = mesh
        self.engine = ISPGraph(partition_graph(g, mesh.shape[axis]), mesh,
                               axis=axis)
        self._key = jax.random.key(seed)
        fanouts_ = self.fanouts
        eng = self.engine

        def prepare(targets, key):
            hops = eng.sample_khop(targets, fanouts_, key=key)
            hop_feats = [eng.gather_features(h) for h in hops]
            labels = eng.gather_labels(hops[0])
            return hops, hop_feats, labels

        self._prepare = jax.jit(prepare)
        self._jnp = jnp
        self._jax = jax

    def get_batch(self, idx: int) -> Minibatch:
        targets = self.targets(idx)
        self.impose_storage_cost(idx)
        key = self._jax.random.fold_in(self._key, idx)
        with self.mesh:
            hops, hop_feats, labels = self._prepare(
                self._jnp.asarray(targets), key)
        return Minibatch(targets=targets, hop_ids=list(hops),
                         hop_feats=list(hop_feats), labels=labels)


# ---------------------------------------------------------------------------
# pallas backend — in-storage-style kernels on one device
# ---------------------------------------------------------------------------

@register_loader("pallas")
class PallasSubgraphLoader(_LoaderBase):
    """Kernel data preparation: the ``neighbor_sample`` Pallas kernel run
    k-hop (HBM edge array, VMEM block staging) composed with the
    ``feature_gather`` row-gather kernel — the paper's ISP firmware loop on
    the TPU memory hierarchy, feeding real training.

    Either array family can read through an HBM cache tier instead of a
    full upload (``core.config.CacheTierSpec``, tier='device'):

    * ``device_cache`` (arrays containing 'features'): an HBM-resident
      ``DeviceFeatureCache`` — the batch's unique node ids are resolved
      against the cache, misses are fetched through the GraphStore
      (in-memory or real paged DiskStore reads) and admitted by the
      host-managed policy, and rows are gathered on-device by the
      ``feature_gather_cached`` kernel.
    * ``edge_cache`` (arrays containing 'topology'): a
      ``DeviceEdgeBlockCache`` in front of the CSR ``indices`` array —
      sampling dispatches the ``neighbor_sample_cached`` kernel, which
      reads each target's two edge blocks through the cache's slot
      indirection, so the edge array too stays off-device (the ROADMAP's
      out-of-core-topology path).  Frontiers whose block working set
      exceeds the cache are sampled in planned chunks.

    Under a ``PrefetchingLoader`` the admission uploads run in the
    prefetch worker, overlapping the consumer's train step.  Training is
    bit-identical to the full uploads at equal seeds; per-batch
    hit/miss/eviction counters land in ``Minibatch.trace.io`` —
    ``'devcache'`` and ``'edgecache'`` blocks next to the host
    page-cache counters."""

    def __init__(self, g, *, batch_size, fanouts, mesh=None, seed=0,
                 storage_engine=None, store=None, sampler="khop",
                 walk_length=4, device_cache=None, edge_cache=None):
        super().__init__(g, batch_size=batch_size, fanouts=fanouts,
                         seed=seed, storage_engine=storage_engine,
                         store=store, sampler=sampler,
                         walk_length=walk_length)
        import jax
        import jax.numpy as jnp
        from repro.kernels import ops
        from repro.kernels.layout import to_rows
        self._devcache_bypass = False   # permanent once tripped
        self._bypass_events = 0
        self.indptr = jnp.asarray(g.indptr, jnp.int32)
        # labels live on device too: the per-batch gather happens inside
        # the jitted prepare, not via host numpy indexing per call
        self.labels = jnp.asarray(g.labels, jnp.int32)
        self.max_degree = int(g.degrees().max()) if g.num_edges else 1
        self._key = jax.random.key(seed)
        self._ops = ops
        self._jnp = jnp
        self._jax = jax
        fanouts_ = self.fanouts
        maxd = self.max_degree
        self.feat_dim = width = g.feat_dim

        use_feat_cache = (device_cache is not None
                          and getattr(device_cache, "rows", 0))
        use_edge_cache = (edge_cache is not None
                          and getattr(edge_cache, "edge_blocks", 0))
        if use_feat_cache or use_edge_cache:
            from repro.storage.devcache import pad_pow2
            self._pad_pow2 = pad_pow2

        if use_edge_cache:
            from repro.storage.devcache import DeviceEdgeBlockCache
            self.indices = None         # topology stays off-device
            self.edgecache = DeviceEdgeBlockCache(
                self.store, indptr=np.asarray(g.indptr, np.int64),
                block_e=ops.edge_block_size(maxd),
                blocks=edge_cache.edge_blocks, policy=edge_cache.policy,
                pinned_fraction=edge_cache.pinned_fraction)
        else:
            self.indices = jnp.asarray(g.indices, jnp.int32)

        if use_feat_cache:
            from repro.storage.devcache import DeviceFeatureCache
            self.features = None        # the whole point: no full upload
            self.devcache = DeviceFeatureCache(
                self.store, rows=device_cache.rows,
                policy=device_cache.policy,
                pinned_fraction=device_cache.pinned_fraction)
        else:
            # uploaded once in the kernels' (N, 1, Wp) row layout
            self.features = jnp.asarray(
                to_rows(np.asarray(g.features, np.float32)))

        if use_edge_cache:
            self._prepare = self._sample = None
        elif use_feat_cache:
            @jax.jit
            def sample(indptr, indices, labels, targets, key):
                hops = ops.sample_khop_kernel(indptr, indices, targets,
                                              fanouts_, key=key,
                                              max_degree=maxd)
                return hops, jnp.take(labels, targets)

            self._sample = sample
            self._prepare = None
        else:
            @jax.jit
            def prepare(indptr, indices, features, labels, targets, key):
                hops = ops.sample_khop_kernel(indptr, indices, targets,
                                              fanouts_, key=key,
                                              max_degree=maxd)
                hop_feats = [ops.feature_gather_rows(features, h,
                                                     width=width)
                             for h in hops]
                batch_labels = jnp.take(labels, targets)
                return hops, hop_feats, batch_labels

            self._prepare = prepare
            self._sample = None

    def get_batch(self, idx: int) -> Minibatch:
        if self.devcache is None and self.edgecache is None:
            self._advance_oracle(idx)
            targets = self.targets(idx)
            self.impose_storage_cost(idx)
            key = self._jax.random.fold_in(self._key, idx)
            hops, hop_feats, labels = self._prepare(
                self.indptr, self.indices, self.features, self.labels,
                self._jnp.asarray(targets), key)
            return Minibatch(targets=targets, hop_ids=list(hops),
                             hop_feats=list(hop_feats), labels=labels)
        # the cached data plane is the staged composition — the same
        # three functions the OverlappedLoader runs on separate lanes,
        # executed back-to-back here, so sync and overlapped training are
        # bit-identical by construction
        return self._stage_admit(self._stage_resolve(self._stage_sample(idx)))

    # -- the staged cached data plane ----------------------------------------
    # Stage contract (pipeline.OverlappedLoader): stage 0 maps a batch
    # index to a payload, later stages map the payload forward; each stage
    # is called strictly in batch order within its lane.  Cache-mirror
    # bookkeeping happens only in plan_rows (resolve lane, serial) and
    # device mutations replay in plan order (admit lane, serial), so
    # results are bit-identical to running the three stages inline.

    def pipeline_stages(self):
        """The overlapped decomposition of the cached path: sample the
        k-hop (edge-block cache traffic included), resolve feature-cache
        misses (storage preads), admit + gather on device.  ``None`` for
        the full-upload configuration — there is nothing to overlap."""
        if self.devcache is None and self.edgecache is None:
            return None
        return [("sample", self._stage_sample),
                ("resolve", self._stage_resolve),
                ("admit", self._stage_admit)]

    def _attr(self, ctx):
        """Attribution scope for batch-owned store reads: bill ``ctx``
        even when the store fans the read out to its pread pool."""
        if ctx is None:
            return contextlib.nullcontext()
        return self.store.io_attribution(ctx)

    def _stage_sample(self, idx: int) -> dict:
        """Sample the k-hop (through the edge-block cache when configured,
        else the device-resident edge array).  The RNG streams are
        untouched and the staged block contents are exact — bit-identity
        holds for every cache combination.  The edge-block cache is owned
        entirely by this lane (plan+resolve+dispatch per hop), so its
        counters delta here is the batch's exact edge traffic."""
        self._advance_oracle(idx)
        targets = self.targets(idx)
        self.impose_storage_cost(idx)
        key = self._jax.random.fold_in(self._key, idx)
        make_ctx = getattr(self.store, "make_io_context", None)
        ctx = make_ctx() if make_ctx is not None else None
        if ctx is not None:
            # spans of pool preads issued on this batch's behalf inherit
            # the attribution ctx — and with it the batch index
            ctx.batch = idx
        io0 = _io_snapshot(self.store) if ctx is None else None
        edge0 = (self.edgecache.counters()
                 if self.edgecache is not None else None)
        with self._attr(ctx):
            if self.edgecache is not None:
                hops, labels = self._sample_khop_edgecached(targets, key)
            else:
                hops, labels = self._sample(self.indptr, self.indices,
                                            self.labels,
                                            self._jnp.asarray(targets), key)
        edge_io = None
        if edge0 is not None:
            e1 = self.edgecache.counters()
            edge_io = {k: e1[k] - edge0[k] for k in e1}
        return dict(idx=idx, targets=targets, hops=hops, labels=labels,
                    ctx=ctx, io0=io0, edge_io=edge_io)

    def reset_staged_state(self) -> None:
        """Discard cache-mirror state staged by abandoned in-flight plans
        (``OverlappedLoader`` calls this before a deterministic lane
        replay): every planned-but-never-installed slot would otherwise
        stay marked resident forever — a ghost entry serving garbage."""
        if self.devcache is not None and not self._devcache_bypass:
            self.devcache.reset()
        if self.edgecache is not None:
            self.edgecache.reset()

    def _note_devcache_failure(self, exc: BaseException) -> None:
        """Degrade policy: a feature-cache fetch that failed *past* the
        store's own retry budget means the cached path cannot make
        progress — bypass it permanently (direct ``gather_features``
        per batch) rather than failing training."""
        self._devcache_bypass = True
        self._bypass_events += 1
        warnings.warn(
            f"device feature cache fetch failed past the retry policy "
            f"({exc}); bypassing the cache permanently — features now "
            f"fetched directly from the store each batch (slower, "
            f"bit-identical)", stacklevel=2)
        try:
            self.devcache.reset(preload=False)
        except Exception:
            pass                        # device state is unreachable anyway

    def _stage_resolve(self, s: dict) -> dict:
        """Plan + fetch the batch's feature-cache misses.  The plan is
        made serially in batch order under the cache lock (reserving
        slots and mirror state — the reserved-slot handoff), then the
        miss rows are pread from storage with no lock held; the store may
        split the reads across its pool, billed to this batch's ctx."""
        np_ = np
        hop_ids = [np_.asarray(h) for h in s["hops"]]
        uniq = np_.unique(np_.concatenate([h.reshape(-1) for h in hop_ids]))
        s["hop_ids"], s["uniq"] = hop_ids, uniq
        if self.devcache is not None and not self._devcache_bypass:
            # dispatch-pad the unique set to a power of two (repeating the
            # last id, so pads are cache hits): U varies every batch, and
            # an unbucketed width would recompile the downstream take per
            # batch
            try:
                self.devcache.oracle_begin_batch(s["idx"])
                with self._attr(s["ctx"]):
                    with obs.trace_span("devcache.plan", batch=s["idx"]):
                        plan = self.devcache.plan_rows(
                            self._pad_pow2(uniq, uniq[-1]),
                            n_valid=uniq.size)
                    with obs.trace_span("devcache.fetch", batch=s["idx"]):
                        self.devcache.fetch_plan(plan)
                s["plan"] = plan
            except StoreReadError as e:
                self._note_devcache_failure(e)
                s["plan"] = None
        return s

    def _stage_admit(self, s: dict) -> Minibatch:
        """Install the fetched rows (H2D upload), gather on device, and
        assemble the Minibatch with the batch's exact io attribution.
        With the feature cache bypassed (``_note_devcache_failure``) the
        batch's unique rows are fetched straight from the store instead —
        the same rows in the same order, so training stays bit-identical;
        only the transfer volume and counters differ."""
        jnp, np_ = self._jnp, np
        hop_ids, uniq = s["hop_ids"], s["uniq"]
        plan = s.get("plan")
        if self.devcache is not None and plan is not None:
            with obs.trace_span("devcache.install", batch=s["idx"]):
                rows = self.devcache.execute_plan(plan)
            F = self.devcache.feat_dim
            hop_feats = []
            for h in hop_ids:
                pos = np_.searchsorted(uniq, h.reshape(-1))
                hop_feats.append(jnp.take(rows, jnp.asarray(pos, jnp.int32),
                                          axis=0).reshape(h.shape + (F,)))
        elif self.devcache is not None:
            # bypass path: direct store gather of the batch's unique rows
            with self._attr(s["ctx"]):
                rows = jnp.asarray(self.store.gather_features(uniq),
                                   jnp.float32)
            F = int(rows.shape[1])
            hop_feats = []
            for h in hop_ids:
                pos = np_.searchsorted(uniq, h.reshape(-1))
                hop_feats.append(jnp.take(rows, jnp.asarray(pos, jnp.int32),
                                          axis=0).reshape(h.shape + (F,)))
        else:
            hop_feats = [self._ops.feature_gather_rows(
                self.features, h, width=self.feat_dim) for h in s["hops"]]
        if s["ctx"] is not None:
            io = s["ctx"].counters()
        else:
            io = _io_delta(self.store, s["io0"]) or {}
        io = nest_fault_counters(io)
        if self.devcache is not None:
            if plan is not None:
                io["devcache"] = dict(plan.counters)
            else:
                io["devcache_bypass"] = True
        if s["edge_io"] is not None:
            io["edgecache"] = s["edge_io"]
        trace = SampleTrace(touched_nodes=np_.empty(0, np_.int64),
                            hops=hop_ids, subgraph_nodes=uniq, io=io)
        return Minibatch(targets=s["targets"], hop_ids=list(s["hops"]),
                         hop_feats=hop_feats, labels=s["labels"],
                         trace=trace)

    def stats(self) -> dict:
        return dict(super().stats(),
                    devcache_bypass=self._devcache_bypass,
                    devcache_bypass_events=self._bypass_events)

    def warm_batch(self, idx: int) -> int:
        """Frontier planner hook: pre-pull batch ``idx``'s probable byte
        ranges (its targets' neighbor lists and feature rows) through the
        store's page cache on the pread pool.  Advisory — warms only the
        host page cache, never device or cache-mirror state."""
        warm = getattr(self.store, "warm_nodes", None)
        if warm is None:
            return 0
        return warm(self.targets(idx),
                    features=self.devcache is not None,
                    edges=self.edgecache is not None)

    def _sample_khop_edgecached(self, targets, key):
        """K-hop sampling through the HBM edge-block cache.

        The key/rand derivation matches ``ops.sample_khop_kernel``
        bit-for-bit; only the kernel's edge reads differ (cache slots
        instead of the full array), and the staged block contents are
        identical — so sampled IDs match the uncached path exactly.
        Hops run at the host level because each hop's frontier must be
        resolved (admitted) before its kernel dispatches."""
        jax_, jnp = self._jax, self._jnp
        frontier = np.asarray(targets, np.int32)
        hops = [jnp.asarray(frontier)]
        for i, f in enumerate(self.fanouts):
            rand = jax_.random.randint(jax_.random.fold_in(key, i),
                                       frontier.shape + (f,), 0, 2**31 - 1)
            flat = frontier.reshape(-1)
            nxt = self._sample_chunk_cached(flat,
                                            rand.reshape(flat.shape[0], f))
            frontier = nxt.reshape(frontier.shape + (f,))
            hops.append(jnp.asarray(frontier))
        labels = jnp.take(self.labels, jnp.asarray(targets))
        return hops, labels

    def _sample_chunk_cached(self, flat, rand2d) -> np.ndarray:
        """One hop through the edge-block cache: plan chunks whose block
        working set fits the cache, resolve (admit) each chunk's blocks,
        dispatch the cached kernel per chunk.  Chunk dispatch lengths are
        pow2-padded with node 0 (whose blocks every plan keeps resident)
        so retracing stays bounded when the planner has to split."""
        ec = self.edgecache
        jnp = self._jnp
        parts = []
        for sl, blocks in ec.plan(flat):
            ec.resolve(blocks)
            seg = flat[sl]
            seg_rand = rand2d[sl]
            n = seg.shape[0]
            width = 1 << (n - 1).bit_length()
            if width > n:
                seg = np.concatenate([seg, np.zeros(width - n, seg.dtype)])
                seg_rand = jnp.concatenate(
                    [seg_rand, jnp.zeros((width - n, seg_rand.shape[1]),
                                         seg_rand.dtype)])
            out = self._ops.neighbor_sample_cached(
                self.indptr, ec.table, ec.slot_of,
                jnp.asarray(seg, jnp.int32), seg_rand,
                block_e=ec.block_e, max_block=ec.max_block)
            parts.append(np.asarray(out[:n]))
        return parts[0] if len(parts) == 1 else np.concatenate(parts)


# ---------------------------------------------------------------------------
# generic consumer — one train step / training loop for every backend
# ---------------------------------------------------------------------------

def build_train_step(loader, gnn, optimizer, mesh=None, rules=None):
    """Generic GraphSAGE update over any backend's ``Minibatch``.

    The jit region covers loss + grads + optimizer (state donated); data
    preparation happens in the loader, so the same consumer serves host
    numpy batches and device-resident isp/pallas batches.  (The fused
    sample-inside-jit ISP step remains available as
    ``core.isp.build_isp_train_step``.)
    """
    import jax
    import jax.numpy as jnp
    from repro.core.gnn import gnn_loss_fn

    if loader is not None and tuple(loader.fanouts) != tuple(gnn.cfg.fanouts):
        raise ValueError(f"loader fanouts {loader.fanouts} != "
                         f"gnn fanouts {gnn.cfg.fanouts}")

    def loss_fn(params, hop_feats, labels):
        return gnn_loss_fn(gnn, params, hop_feats, labels, mesh, rules)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, hop_feats, labels):
        (_, metrics), grads = grad_fn(state["params"], hop_feats, labels)
        new_params, new_opt, opt_metrics = optimizer.update(
            grads, state["opt"], state["params"], state["step"])
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, dict(metrics, **opt_metrics))

    def train_step(state, mb: Minibatch):
        hop_feats = [jnp.asarray(f, jnp.float32) for f in mb.hop_feats]
        return step(state, hop_feats, jnp.asarray(mb.labels, jnp.int32))

    return train_step


@dataclasses.dataclass
class RunStats:
    """Shared loop telemetry: the paper's Fig. 7 metrics for any backend."""

    steps: int = 0
    idle_s: float = 0.0          # consumer waiting on data preparation
    busy_s: float = 0.0          # consumer in the train step
    wall_s: float = 0.0

    @property
    def idle_fraction(self) -> float:
        return _idle_fraction(self.idle_s, self.busy_s)

    @property
    def steps_per_s(self) -> float:
        return self.steps / self.wall_s if self.wall_s > 0 else 0.0


def train_loop(loader, train_step, state, *, steps: int, start: int = 0,
               on_step=None) -> tuple[object, RunStats]:
    """Drive ``train_step`` over ``loader`` batches; record idle/busy split.

    ``on_step(i, state, metrics)`` is called after every step (logging,
    checkpointing).  Returns the final state and the run telemetry.
    Each iteration is a ``train`` step marker (``obs.step_span``) in any
    active profiler trace.
    """
    import jax

    stats = RunStats()
    t_start = time.perf_counter()
    for i in range(start, steps):
        with obs.step_span("train", i):
            t0 = time.perf_counter()
            with obs.trace_span("consume.wait", batch=i, lane="consumer"):
                mb = loader.get_batch(i)
            t1 = time.perf_counter()
            with obs.trace_span("consume.step", batch=i, lane="consumer"):
                state, metrics = train_step(state, mb)
                # async dispatch would otherwise push device compute into
                # the next step's idle window and skew the idle/busy split
                jax.block_until_ready(metrics)
            t2 = time.perf_counter()
            stats.idle_s += t1 - t0
            stats.busy_s += t2 - t1
            stats.steps += 1
            obs.tick()               # periodic JSONL metrics snapshot
            if on_step is not None:
                on_step(i, state, metrics)
    stats.wall_s = time.perf_counter() - t_start
    return state, stats
