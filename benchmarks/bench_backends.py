"""Backend throughput benchmark: steps/s + consumer-idle fraction for each
data-preparation backend (host / isp / pallas) feeding the same GraphSAGE
consumer — the live-training version of the paper's backend comparison.

Row keys encode the configuration: ``host``, ``host@disk``,
``pallas@devcache`` (HBM feature cache over in-memory backing),
``pallas@disk+devcache`` (HBM cache missing to real paged disk reads),
``host@saint`` (GraphSAINT walks), ...  When ``--device-cache-rows`` is
set, the full-upload ``pallas`` baseline row rides along, so one run
holds both sides of the cached-vs-uploaded comparison.  Each row's
``loader_stats`` carries the cache counters twice: the ``store`` /
``devcache`` blocks are cumulative (warmup and preload included), while
``store_epoch`` / ``devcache_epoch`` cover the window since
``loader.start_epoch()`` was called (after warmup) — use the ``_epoch``
views for hit-rate curves comparable across runs.  Caveat: async
production runs ahead of consumption, so for the host backend the
epoch boundary is fuzzy by the producer queue depth (sharp for the
synchronous device backends; use small queue depths when exact
windows matter).

Rows are configured through the declarative spec API: the shared
spec-generated flags assemble one ``PipelineSpec`` per row (built by
``build_pipeline``), or ``--spec a.json,b.json`` runs checked-in spec
files verbatim (``benchmarks/specs/*.json`` — what CI drives).  Every
result row embeds the exact spec JSON that produced it.

``--contention-workers`` additionally runs the DiskStore contention
micro-benchmark: producer threads hammer the paged read path with the
page-cache lock sharded vs. global.  It accepts a single count, a comma
list, or an inclusive range (``4-12`` / ``4-12:2``); each point is
measured against the Fig. 17 engine contention model
(``engines.throughput()``), so the JSON holds the measured and modelled
scaling curves side by side.  ``--admission-bench`` adds devcache
admission-overhead rows (batched numpy bookkeeping) at 10-100k unique
rows/batch.

``--wire-bench`` is the paper's headline figure: every ``host@disk``
row gets an in-storage-processing twin (``StoreSpec.mode="isp"`` — the
sampler runs inside a spawned storage-server process and only sampled
bytes cross the wire), and the payload's ``wire_bench`` block compares
bytes-over-the-wire against the host row's bytes-read-from-store,
gated on bit-identical final loss.  Run it out-of-core
(``--dataset reddit --large-scale`` with a small ``--cache-mb``) — with
a warm page cache on a toy graph the raw-bytes side is artificially
tiny and the inequality is meaningless.  ``--directio-calibrate``
records the measured-pread calibration of the ``DirectIOEngine`` cost
constants (``engines.calibrate_directio``).

Run:  PYTHONPATH=src python benchmarks/bench_backends.py
Emits BENCH_backends.json (the perf-trajectory seed) and prints one line
per backend.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


def sampler_locality(g, sampler: str, *, steps: int, batch: int, fanouts,
                     walk_length: int, seed: int = 0) -> dict:
    """Block-request locality of a sampler family (paper §VI-F): replay
    ``steps`` batches of its access trace against the 4 KB block view of
    the edge array and report how many blocks each request touches and
    how often blocks repeat within a batch (the reuse a page cache can
    harvest)."""
    import numpy as np

    from repro.core import batch_targets, sample_khop, saint_random_walk
    from repro.storage import block_trace

    requests = total = unique = 0
    for i in range(steps):
        targets = batch_targets(g, i, batch, seed)
        if sampler == "saint":
            trace = saint_random_walk(g, targets, walk_length, seed=seed + i)
        else:
            trace = sample_khop(g, targets, fanouts, seed=seed + i)
        bt = block_trace(g, trace.touched_nodes)
        requests += bt.n_requests
        total += bt.total_blocks
        unique += bt.unique_blocks
    return {"sampler": sampler, "batches": steps, "requests": requests,
            "total_blocks": total, "unique_blocks": unique,
            "blocks_per_request": total / max(requests, 1),
            "block_reuse": total / max(unique, 1)}


def contention_bench(store_dir: str, *, n_workers: int, batches: int,
                     batch: int, fanouts, cache_mb: float | None,
                     policy: str | None = None,
                     lock_shards: int | None = None) -> dict:
    """Multi-producer DiskStore scaling: ``n_workers`` threads produce
    disjoint batches through one shared store, with the page-cache lock
    global (shards=1) vs. hashed-block sharded (``lock_shards``, default
    the storage spec's).  Reports wall time and aggregate batches/s for
    both (the ROADMAP's Fig. 17 contention measurement)."""
    import threading

    from repro.core import batch_targets, sample_khop
    from repro.storage import DiskStore

    # warm the OS page cache over the store's files once, so both arms
    # measure lock behavior rather than cold-read order
    for name in os.listdir(store_dir):
        with open(os.path.join(store_dir, name), "rb") as f:
            while f.read(1 << 20):
                pass

    def run(lock_shards: int | None) -> dict:
        store = DiskStore(store_dir, cache_mb=cache_mb, policy=policy,
                          lock_shards=lock_shards)
        try:
            def worker(w: int):
                for i in range(batches):
                    idx = w * batches + i
                    targets = batch_targets(store, idx, batch, 0)
                    trace = sample_khop(store, targets, fanouts, seed=idx)
                    for h in trace.hops:
                        store.gather_features(h)
            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(n_workers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            io = store.io_counters()
        finally:
            store.close()
        return {"lock_shards": store.lock_shards, "wall_s": dt,
                "batches_per_s": n_workers * batches / dt,
                "block_fetches": io["block_fetches"], "hits": io["hits"],
                "misses": io["misses"]}

    sharded = run(lock_shards)      # None = spec default shard count
    global_lock = run(1)
    return {"workers": n_workers, "batches_per_worker": batches,
            "global": global_lock, "sharded": sharded,
            "speedup": sharded["batches_per_s"]
            / max(global_lock["batches_per_s"], 1e-9)}


def _parse_workers(text: str) -> list[int]:
    """Worker counts from ``--contention-workers``: ``6``, ``4,8,12``,
    or an inclusive range ``4-12`` / ``4-12:2`` (start-end[:step])."""
    out: list[int] = []
    for part in str(text).split(","):
        part = part.strip()
        if not part:
            continue
        if "-" in part:
            span, _, step = part.partition(":")
            a, b = span.split("-")
            out.extend(range(int(a), int(b) + 1, int(step or 1)))
        else:
            out.append(int(part))
    return [w for w in out if w > 0]


def contention_model(g, workers: list[int], *, batch: int, fanouts,
                     steps: int = 4, seed: int = 0) -> dict:
    """The Fig. 17 contention model for the measured sweep: per-batch
    cost of the ``mmap`` engine (the OS-page-cache device model standing
    behind DiskStore's paged reads) replayed over real sampler traces,
    pushed through ``engines.throughput()`` at each worker count.  The
    absolute batches/s are device-model numbers, not this machine's —
    compare the *scaling* curves (both normalised to the first worker
    count)."""
    import numpy as np

    from repro.core import batch_targets, sample_khop
    from repro.storage import engines as eng

    engine = eng.make_engine("mmap", g)
    costs = [engine.batch_cost(
        sample_khop(g, batch_targets(g, i, batch, seed), fanouts,
                    seed=seed + i)) for i in range(steps)]
    resources = set().union(*[c.shared_demand for c in costs])
    mean = eng.BatchCost(
        "mmap", float(np.mean([c.time_s for c in costs])), 0, 0, {},
        {r: float(np.mean([c.shared_demand.get(r, 0.0) for c in costs]))
         for r in resources})
    bps = {w: eng.throughput(mean, w) for w in workers}
    base = max(bps[workers[0]], 1e-12)
    return {"engine": "mmap", "batch_time_s": mean.time_s,
            "batches_per_s": {str(w): bps[w] for w in workers},
            "scaling": {str(w): bps[w] / base for w in workers}}


def _wire_bench_report(results: dict) -> dict:
    """Pair every isp-mode row with its local-mode disk twin and emit
    the headline comparison: ISP wire bytes (request + reply frames, both
    directions) vs the host row's bytes-read-from-store, plus the
    storage-side raw bytes the server itself read from flash — gated on
    bit-identical final loss."""
    pairs = []
    for row, r in results.items():
        if (r["spec"]["store"].get("mode") or "local") != "isp":
            continue
        twin = next(
            ((row2, r2) for row2, r2 in results.items()
             if (r2["spec"]["store"].get("mode") or "local") == "local"
             and r2["graph_store"] == "disk"
             and r2["spec"]["backend"]["name"]
             == r["spec"]["backend"]["name"]),
            None)
        if twin is None:
            continue
        host_row, host = twin
        m, hm = r["metrics"], host["metrics"]
        tx = int(m.get("isp.bytes_tx", 0))
        rx = int(m.get("isp.bytes_rx", 0))
        wire = tx + rx
        host_raw = int(hm.get("store.bytes_fetched", 0))
        server_raw = int(m.get("store.bytes_fetched", 0))
        pairs.append({
            "isp_row": row, "host_row": host_row,
            "isp_bytes_tx": tx, "isp_bytes_rx": rx, "wire_bytes": wire,
            "host_bytes_read": host_raw,
            "isp_server_bytes_read": server_raw,
            "wire_to_host_raw_ratio": wire / max(host_raw, 1),
            "wire_lt_host_raw": wire < host_raw,
            "wire_lt_server_raw": wire < server_raw,
            "steps_per_s": {"isp": r["steps_per_s"],
                            "host": host["steps_per_s"]},
            "loss_bit_identical":
                r["final_loss"] == host["final_loss"],
        })
    return {"pairs": pairs,
            "all_bit_identical": all(p["loss_bit_identical"]
                                     for p in pairs),
            "all_wire_lt_host_raw": all(p["wire_lt_host_raw"]
                                        for p in pairs)}


def admission_bench(sizes=(10_000, 30_000, 100_000), *, rows: int = 32_768,
                    feat_dim: int = 8, repeats: int = 3) -> list[dict]:
    """Devcache admission-overhead microbench: time ``gather_rows`` over
    batches of 10-100k unique rows against a cache far below the working
    set, per policy.  Feature width is kept small so the measurement is
    the *bookkeeping* (batched numpy LRU/pinned admission + scatter
    dispatch), not the row copy.  Solo timed runs after a warmup batch;
    best-of-``repeats`` per size."""
    import jax
    import numpy as np

    from repro.core.graph import attach_features, rmat_graph
    from repro.storage import DeviceFeatureCache

    n = 1 << 18
    g = attach_features(rmat_graph(n, 1 << 19, seed=7, name="admission"),
                        feat_dim)
    out = []
    for policy in ("lru", "pinned"):
        dc = DeviceFeatureCache(g, rows=rows, policy=policy)
        jax.block_until_ready(
            dc.gather_rows(np.arange(rows // 2)))       # warm the jits
        for size in sizes:
            rng = np.random.default_rng(size)
            best = float("inf")
            for _ in range(repeats):
                ids = np.unique(rng.integers(0, n, size * 2))[:size]
                t0 = time.perf_counter()
                jax.block_until_ready(dc.gather_rows(ids))
                best = min(best, time.perf_counter() - t0)
            row = {"policy": policy, "unique_rows": int(size),
                   "seconds_per_batch": best,
                   "rows_per_s": size / best}
            out.append(row)
            print(f"bench_backends,admission,{policy},{size},"
                  f"rows_per_s,{row['rows_per_s']:.4g}")
    return out


def _host_tier_counters(store_dir: str, policy: str, cap_mb: float, *,
                        steps: int, batch: int, fanouts, seed: int,
                        window: int) -> dict:
    """Exact page-cache counters for one host-tier sweep point: a
    single-threaded replay of the host producer's access pattern
    (sample + per-hop feature gathers + labels), so the lru/pinned/
    optimal comparison is deterministic — no producer-lookahead fuzz."""
    import numpy as np

    from repro.core import batch_targets, sample_khop
    from repro.storage import DiskStore

    store = DiskStore(store_dir, cache_mb=cap_mb, policy=policy)
    try:
        if policy == "optimal":
            from repro.storage.oracle import OracleReplayer, RawDiskReader
            raw = RawDiskReader(store)

            def replay(idx):
                t = batch_targets(store, idx, batch, seed)
                tr = sample_khop(raw, t, fanouts, seed=seed + idx)
                return {"pages": store.replay_block_ids(
                    feature_nodes=tr.subgraph_nodes,
                    edge_nodes=np.unique(tr.touched_nodes),
                    label_nodes=t)}

            store.oracle_attach(OracleReplayer(
                replay, {"pages": store.oracle_feed}, window=window,
                name="sweep"))
        for i in range(steps):
            store.oracle_advance(i)
            targets = batch_targets(store, i, batch, seed)
            trace = sample_khop(store, targets, fanouts, seed=seed + i)
            for h in trace.hops:
                store.gather_features(h)
            store.gather_labels(targets)
        io = store.io_counters()
    finally:
        store.close()
    return io


def policy_sweep(args, g, mesh, rules, store_dir: str) -> dict:
    """The headline curves: hit rate and steps/s vs cache capacity for
    lru / pinned / optimal, on both cache tiers.

    * host tier: the DiskStore page cache under the host backend's
      access pattern, swept over ``--sweep-cache-mb``.  Hit rates come
      from a deterministic single-threaded counter replay
      (``_host_tier_counters``); steps/s from a live training run.
    * device tier: the HBM feature cache under disk-backed pallas,
      swept over ``--sweep-device-rows``; the sync cached path is
      deterministic, so one training run yields both.

    ``optimal`` is the Belady ceiling computed by sampler replay
    (storage/oracle.py); the sweep records per-point ``miss_le_lru``
    and per-capacity loss bit-identity so regressions are visible in
    the JSON, not just the curves."""
    import jax
    import jax.numpy as jnp

    from repro.core import (GNNConfig, GraphSAGE, build_pipeline,
                            build_train_step, train_loop)
    from repro.core.config import (BackendSpec, CacheTierSpec, PipelineSpec,
                                   SamplerSpec, StoreSpec)
    from repro.optim import adamw

    policies = ("lru", "pinned", "optimal")
    host_caps = [float(x) for x in args.sweep_cache_mb.split(",")]
    dev_caps = [int(x) for x in args.sweep_device_rows.split(",")]
    w_host = args.cache_oracle_window or 8
    w_dev = args.device_cache_oracle_window or 8
    gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim, hidden=args.hidden,
                              n_classes=int(g.labels.max()) + 1,
                              fanouts=args.fanouts))
    opt = adamw(1e-3)

    def train_point(spec, counter_key):
        pipe = build_pipeline(spec, g, mesh=mesh)
        try:
            step = build_train_step(pipe, gnn, opt, mesh, rules)
            p = gnn.init(jax.random.key(0))
            state = {"params": p, "opt": opt.init(p),
                     "step": jnp.zeros((), jnp.int32)}
            losses = []
            track = lambda i, s, m: losses.append(float(m["loss"]))  # noqa: E731
            with mesh:
                state, _ = train_loop(pipe, step, state, steps=args.warmup)
                pipe.start_epoch()
                state, stats = train_loop(pipe, step, state,
                                          steps=args.warmup + args.steps,
                                          start=args.warmup, on_step=track)
            ls = pipe.stats()
        finally:
            pipe.close()
        c = ls.get(counter_key) or {}
        return (dict(hits=int(c.get("hits", 0)),
                     misses=int(c.get("misses", 0)),
                     evictions=int(c.get("evictions", 0))),
                stats.steps_per_s, repr(losses[-1]))

    rows = []
    for cap in host_caps:
        for policy in policies:
            io = _host_tier_counters(
                store_dir, policy, cap, steps=args.warmup + args.steps,
                batch=args.batch, fanouts=args.fanouts, seed=args.seed,
                window=w_host)
            spec = PipelineSpec(
                backend=BackendSpec(name="host", n_workers=1,
                                    queue_depth=2),
                sampler=SamplerSpec(family="khop", fanouts=args.fanouts,
                                    walk_length=args.walk_length),
                store=StoreSpec(kind="disk", path=store_dir),
                cache_tiers=(CacheTierSpec(
                    tier="host", policy=policy, capacity_mb=cap, arrays=(),
                    oracle_window=w_host if policy == "optimal" else 0),),
                batch_size=args.batch, seed=args.seed)
            _, sps, loss = train_point(spec, "store_epoch")
            hits, misses = int(io["hits"]), int(io["misses"])
            rows.append(dict(
                tier="host", policy=policy, capacity_mb=cap,
                hits=hits, misses=misses,
                evictions=int(io["evictions"]),
                hit_rate=hits / max(1, hits + misses),
                steps_per_s=sps, final_loss=loss))
    for rcap in dev_caps:
        for policy in policies:
            spec = PipelineSpec(
                backend=BackendSpec(name="pallas"),
                sampler=SamplerSpec(family="khop", fanouts=args.fanouts,
                                    walk_length=args.walk_length),
                store=StoreSpec(kind="disk", path=store_dir),
                cache_tiers=(
                    CacheTierSpec(tier="host", policy="lru",
                                  capacity_mb=args.cache_mb, arrays=()),
                    CacheTierSpec.device(
                        rows=rcap, policy=policy,
                        pinned_fraction=args.device_cache_pinned_fraction,
                        oracle_window=w_dev if policy == "optimal" else 0)),
                batch_size=args.batch, seed=args.seed)
            c, sps, loss = train_point(spec, "devcache_epoch")
            rows.append(dict(
                tier="device", policy=policy, capacity_rows=rcap,
                hits=c["hits"], misses=c["misses"],
                evictions=c["evictions"],
                hit_rate=c["hits"] / max(1, c["hits"] + c["misses"]),
                steps_per_s=sps, final_loss=loss))

    # per-point checks: Belady must dominate LRU, and policy must never
    # change training values (bit-identical final loss per configuration)
    by_point = {}
    for r in rows:
        cap = r.get("capacity_mb", r.get("capacity_rows"))
        by_point.setdefault((r["tier"], cap), {})[r["policy"]] = r
    all_le, all_bit = True, True
    for (tier, cap), per in by_point.items():
        le = per["optimal"]["misses"] <= per["lru"]["misses"]
        bit = len({per[p]["final_loss"] for p in policies}) == 1
        per["optimal"]["miss_le_lru"] = le
        for p in policies:
            per[p]["loss_bit_identical"] = bit
        all_le &= le
        all_bit &= bit
        cap_s = f"{cap}mb" if tier == "host" else f"{cap}rows"
        for p in policies:
            r = per[p]
            print(f"bench_backends,policy_sweep,{tier},{cap_s},{p},"
                  f"hit_rate,{r['hit_rate']:.4g},misses,{r['misses']},"
                  f"steps_per_s,{r['steps_per_s']:.4g}")
        if not le:
            print(f"bench_backends,policy_sweep,{tier},{cap_s},"
                  f"WARNING,optimal_misses_gt_lru,"
                  f"{per['optimal']['misses']},{per['lru']['misses']}")
    return {"policies": list(policies), "host_capacities_mb": host_caps,
            "device_capacities_rows": dev_caps,
            "oracle_window": {"host": w_host, "device": w_dev},
            "optimal_miss_le_lru": all_le,
            "loss_bit_identical": all_bit, "rows": rows}


def _row_name(spec) -> str:
    """Result-row key encoding a spec's configuration, e.g.
    ``pallas@disk+devcache+edgecache``."""
    suffix = [spec.store.kind] if spec.store.kind != "mem" else []
    if spec.store.mode == "isp":
        suffix.append("isp")
    if spec.store.direct_io:
        suffix.append("directio")
    dev = spec.device_cache_tier()
    if dev is not None and "features" in dev.arrays:
        suffix.append("devcache")
    if dev is not None and "topology" in dev.arrays:
        suffix.append("edgecache")
    if any(t.policy == "optimal" for t in spec.cache_tiers):
        suffix.append("optimal")
    if spec.sampler.family != "khop":
        suffix.append(spec.sampler.family)
    if spec.prefetch.overlap:
        suffix.append("overlap")
    if spec.store.faults is not None:
        suffix.append("faults")
    if spec.obs.enabled:
        suffix.append("obs")
    return spec.backend.name + (f"@{'+'.join(suffix)}" if suffix else "")


def main(argv=None):
    from repro.core.config import (CacheTierSpec, PipelineSpec,
                                   add_pipeline_args,
                                   fill_pipeline_flag_defaults)

    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="reddit")
    ap.add_argument("--large-scale", action="store_true",
                    help="Kronecker-expanded dataset variant")
    ap.add_argument("--backends", default="host,isp,pallas")
    ap.add_argument("--graph-store", default="mem",
                    help="comma list of graph stores to bench: mem and/or "
                         "disk (disk rows run the host backend — and the "
                         "pallas backend when --device-cache-rows or "
                         "--edge-cache-blocks is set — through real paged "
                         "reads)")
    # the per-row data-plane flags are the shared spec-generated surface;
    # --backends/--graph-store above replace the single-valued variants
    add_pipeline_args(ap, exclude=("--backend", "--graph-store"),
                      overrides={"batch": 32})
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--contention-workers", default="0",
                    help="run the DiskStore multi-producer contention "
                         "micro-benchmark: a thread count, comma list, or "
                         "inclusive range 'a-b[:step]' (e.g. '4-12:2'); "
                         "each point is measured against the Fig. 17 "
                         "engine contention model (0 = skip)")
    ap.add_argument("--contention-batches", type=int, default=8,
                    help="batches per contention worker")
    ap.add_argument("--wire-bench", action="store_true",
                    help="add an isp-mode twin (in-storage sampling behind "
                         "a spawned storage-server process) for every "
                         "host@disk row and emit the headline "
                         "bytes-over-wire comparison, gated bit-identical "
                         "(payload key 'wire_bench'); run out-of-core — "
                         "--dataset reddit --large-scale with a small "
                         "--cache-mb — for a meaningful raw-bytes side")
    ap.add_argument("--directio-calibrate", action="store_true",
                    help="measure real pread latencies (O_DIRECT vs "
                         "buffered) on the benched store and record the "
                         "DirectIOEngine cost-constant calibration "
                         "(payload key 'directio_calibration')")
    ap.add_argument("--admission-bench", action="store_true",
                    help="add devcache admission-overhead rows at 10-100k "
                         "unique rows/batch")
    ap.add_argument("--overlap-rows", type=int, choices=(0, 1), default=0,
                    help="1 = also bench an overlapped-pipeline twin of "
                         "every out-of-core row (disk store or device "
                         "cache), so sync and overlapped land side by side")
    ap.add_argument("--policy-sweep", action="store_true",
                    help="sweep lru/pinned/optimal across cache capacities "
                         "on both tiers: hit-rate + steps/s curves with "
                         "the Belady 'optimal' policy as the offline "
                         "ceiling (payload key 'policy_sweep')")
    ap.add_argument("--sweep-cache-mb", default="0.5,1.0,2.0",
                    help="policy sweep: host-tier page-cache capacities "
                         "in MB (comma list)")
    ap.add_argument("--sweep-device-rows", default="256,512,768",
                    help="policy sweep: device-tier feature-cache "
                         "capacities in rows (comma list)")
    ap.add_argument("--out", default="BENCH_backends.json")
    args = ap.parse_args(argv)
    # the bench assembles per-row specs from flag values directly, so
    # resolve the "not given" sentinels to the spec defaults up front
    fill_pipeline_flag_defaults(args)

    import jax
    import jax.numpy as jnp

    from repro.core import (GNNConfig, GraphSAGE, build_pipeline,
                            build_train_step, load_dataset, train_loop)
    from repro.distributed.sharding import ShardingRules
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw

    enable_compile_cache()
    if args.sampler == "saint":
        if args.backends != "host":
            print(f"bench_backends: --sampler saint is host-only; "
                  f"overriding --backends {args.backends!r} -> 'host'")
        args.backends = "host"

    store_kinds = args.graph_store.split(",")
    unknown = set(store_kinds) - {"mem", "disk"}
    if unknown:
        ap.error(f"--graph-store: unknown kind(s) {sorted(unknown)}; "
                 "have mem, disk")

    def make_spec(backend: str, kind: str, with_devcache: bool,
                  store_dir=None, mode: str = "local") -> PipelineSpec:
        from repro.core.config import (BackendSpec, PrefetchSpec,
                                       SamplerSpec, StoreSpec)
        tiers = []
        if kind == "disk":
            tiers.append(CacheTierSpec(
                tier="host", policy=args.cache_policy,
                capacity_mb=args.cache_mb, arrays=(),
                oracle_window=args.cache_oracle_window))
        if with_devcache:
            tiers.append(CacheTierSpec.device(
                rows=args.device_cache_rows,
                edge_blocks=args.edge_cache_blocks,
                policy=args.device_cache_policy,
                pinned_fraction=args.device_cache_pinned_fraction,
                oracle_window=args.device_cache_oracle_window))
        return PipelineSpec(
            backend=BackendSpec(name=backend),
            sampler=SamplerSpec(family=args.sampler, fanouts=args.fanouts,
                                walk_length=args.walk_length),
            store=StoreSpec(kind=kind,
                            mode=mode,
                            path=store_dir if store_dir is not None
                            else args.store_dir,
                            lock_shards=args.lock_shards,
                            io_threads=args.io_threads,
                            direct_io=bool(args.direct_io)
                            if kind == "disk" else False,
                            isp=dict(
                                transport=args.isp_transport,
                                address=args.isp_address,
                                window=args.isp_window,
                                server_cache=bool(args.isp_server_cache))
                            if mode == "isp" else None),
            cache_tiers=tuple(tiers),
            prefetch=PrefetchSpec(depth=args.prefetch,
                                  overlap=bool(args.overlap),
                                  stage_depth=args.stage_depth,
                                  plan_ahead=args.plan_ahead),
            batch_size=args.batch, seed=args.seed,
            engine=args.storage_engine)

    contention_sweep = _parse_workers(args.contention_workers)

    specs: list[PipelineSpec] = []
    if args.spec:
        # spec-driven rows: each file IS one benchmark row, verbatim.
        # One GNN consumes every row, so the specs must agree on the
        # hop-shape contract — fail before any row burns a run.
        specs = [PipelineSpec.load(f) for f in args.spec.split(",")]
        shapes = {s.effective_fanouts for s in specs}
        if len(shapes) > 1:
            ap.error(f"--spec files disagree on effective fanouts "
                     f"{sorted(shapes)}; one GNN serves all rows, so "
                     "bench them in separate runs")
        if args.wire_bench:
            modes = {s.store.mode for s in specs if s.store.kind == "disk"}
            if modes < {"local", "isp"}:
                ap.error("--wire-bench with --spec needs both a "
                         "local-mode and an isp-mode disk spec in the "
                         f"list (got modes {sorted(modes)})")
    else:
        has_device_cache = bool(args.device_cache_rows
                                or args.edge_cache_blocks)
        for kind in store_kinds:
            for backend in args.backends.split(","):
                dc = has_device_cache and backend == "pallas"
                if kind == "disk" and backend != "host" and not dc:
                    print(f"bench_backends: skipping {backend}@disk "
                          "(device backends hold device-resident copies; "
                          "pallas joins the disk rows via "
                          "--device-cache-rows/--edge-cache-blocks)")
                    continue
                if dc and kind == "mem":
                    # the full-upload baseline rides along, so one run
                    # holds both sides of the cached-vs-uploaded comparison
                    specs.append(make_spec(backend, kind, False))
                # isp mode (in-storage sampling) applies to the host
                # backend's disk rows — the device backends hold
                # device-resident copies and never talk to the server
                mode = (args.store_mode
                        if kind == "disk" and backend == "host" else "local")
                specs.append(make_spec(backend, kind, dc, mode=mode))
        if args.overlap_rows:
            import dataclasses as _dc

            from repro.core.config import PrefetchSpec
            specs += [
                s.replace(
                    store=_dc.replace(
                        s.store, io_threads=args.io_threads or 4),
                    prefetch=PrefetchSpec(depth=max(args.prefetch, 2),
                                          overlap=True,
                                          stage_depth=args.stage_depth,
                                          plan_ahead=args.plan_ahead))
                for s in specs
                if s.store.kind == "disk" or s.device_cache_tier()]
        if args.wire_bench:
            # one wire twin per host@disk row (sync rows only — the
            # overlapped twins measure latency hiding, not wire bytes):
            # whichever of local/isp mode the flags produced, add the other
            import dataclasses as _dc

            hostdisk = [s for s in specs
                        if s.backend.name == "host"
                        and s.store.kind == "disk"
                        and not s.prefetch.overlap]
            if not hostdisk:
                ap.error("--wire-bench needs a host@disk row; include "
                         "disk in --graph-store")
            for s in hostdisk:
                if s.store.mode == "local":
                    store = _dc.replace(
                        s.store, mode="isp",
                        isp=dict(transport=args.isp_transport,
                                 address=args.isp_address,
                                 window=args.isp_window,
                                 server_cache=bool(args.isp_server_cache)))
                else:
                    store = _dc.replace(s.store, mode="local", isp=None)
                specs.append(s.replace(store=store))

    fanouts = specs[0].effective_fanouts if specs else args.fanouts
    g = load_dataset(args.dataset, large_scale=args.large_scale)
    mesh = make_host_mesh()
    rules = ShardingRules.default()
    gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim, hidden=args.hidden,
                              n_classes=int(g.labels.max()) + 1,
                              fanouts=fanouts))
    opt = adamw(1e-3)

    store_dir = None
    needs_disk = (any(s.store.kind == "disk" and s.store.path is None
                      for s in specs) or contention_sweep
                  or args.policy_sweep or args.directio_calibrate)
    if needs_disk:
        import atexit
        import shutil
        import tempfile

        from repro.storage import save_graph
        store_dir = tempfile.mkdtemp(prefix=f"graphstore-{args.dataset}-")
        atexit.register(shutil.rmtree, store_dir, ignore_errors=True)
        save_graph(g, store_dir)
        import dataclasses
        specs = [s.replace(store=dataclasses.replace(s.store,
                                                     path=store_dir))
                 if s.store.kind == "disk" and s.store.path is None else s
                 for s in specs]

    results = {}
    for spec in specs:
        row = _row_name(spec)
        n = 2
        while row in results:           # two specs sharing a shape (e.g.
            row = f"{_row_name(spec)}#{n}"      # lru vs pinned) keep
            n += 1                              # separate rows
        pipe = build_pipeline(spec, g, mesh=mesh)
        try:
            step = build_train_step(pipe, gnn, opt, mesh, rules)
            p = gnn.init(jax.random.key(0))
            state = {"params": p, "opt": opt.init(p),
                     "step": jnp.zeros((), jnp.int32)}
            losses = []
            track = lambda i, s, m: losses.append(float(m["loss"]))  # noqa: E731
            with mesh:
                # warmup covers jit compilation + pipeline fill
                state, _ = train_loop(pipe, step, state,
                                      steps=args.warmup)
                # cache counters from here on are the measured
                # epoch's, not cumulative-including-warmup
                pipe.start_epoch()
                state, stats = train_loop(pipe, step, state,
                                          steps=args.warmup + args.steps,
                                          start=args.warmup, on_step=track)
            loader_stats = pipe.stats()
            from repro.obs import names as obs_names
            if pipe.obs is not None:
                # telemetry-enabled rows embed the session's own final
                # snapshot (registry counters + absorbed stats surfaces)
                row_metrics = pipe.obs.registry.snapshot()
            else:
                row_metrics = obs_names.flatten_stats(loader_stats)
            row_metrics.update(obs_names.train_metrics(
                stats.steps, stats.idle_s, stats.busy_s, stats.steps_per_s,
                stats.idle_fraction))
        finally:
            pipe.close()
        results[row] = {
            "steps_per_s": stats.steps_per_s,
            "idle_fraction": stats.idle_fraction,
            "idle_s": stats.idle_s,
            "busy_s": stats.busy_s,
            # repr round-trips the float64 exactly: the overlapped-vs-sync
            # bit-identity gate in CI compares these strings
            "final_loss": repr(losses[-1]) if losses else None,
            # per-row store kind, so tooling can filter rows without
            # string-splitting the legacy top-level comma list
            "graph_store": spec.store.kind,
            "loader_stats": loader_stats,
            # the final metrics snapshot under canonical names
            # (repro.obs.names): the same flat namespace the JSONL
            # sink writes, embedded in every row whether or not the
            # row's spec enabled telemetry
            "metrics": row_metrics,
            # the exact configuration that produced this row, verbatim
            "spec": spec.to_dict(),
        }
        print(f"bench_backends,{args.dataset},{row},"
              f"steps_per_s,{stats.steps_per_s:.4g}")
        print(f"bench_backends,{args.dataset},{row},"
              f"idle_fraction,{stats.idle_fraction:.4g}")
        if "stage_s" in loader_stats:   # overlapped rows: per-stage walls
            means = loader_stats["stage_mean_s"]
            stage_bits = " ".join(f"{k}={means[k] * 1e3:.3g}ms"
                                  for k in loader_stats["stages"])
            print(f"bench_backends,{args.dataset},{row},stage_mean,"
                  f"{stage_bits} "
                  f"overlap_factor={loader_stats['overlap_factor']:.3g}")
        for kind in ("devcache", "edgecache"):
            dcs = loader_stats.get(kind)
            if dcs:
                print(f"bench_backends,{args.dataset},{row},{kind},"
                      f"hits={dcs['hits']} misses={dcs['misses']} "
                      f"evictions={dcs['evictions']}")
        st = loader_stats.get("store", {})
        if any(st.get(k) for k in ("retries", "io_errors", "short_reads",
                                   "corrupt_blocks", "timeouts")):
            print(f"bench_backends,{args.dataset},{row},faults,"
                  f"retries={st['retries']} io_errors={st['io_errors']} "
                  f"short_reads={st['short_reads']} "
                  f"corrupt_blocks={st['corrupt_blocks']} "
                  f"timeouts={st['timeouts']}")
        if loader_stats.get("lane_stall_restarts") or \
                loader_stats.get("degraded"):
            print(f"bench_backends,{args.dataset},{row},lanes,"
                  f"stall_restarts={loader_stats['lane_stall_restarts']} "
                  f"failures={loader_stats['lane_failures']} "
                  f"degraded={loader_stats['degraded']}")

    contention = None
    if contention_sweep:
        sweep_rows = []
        for w in contention_sweep:
            point = contention_bench(
                store_dir, n_workers=w,
                batches=args.contention_batches, batch=args.batch,
                fanouts=fanouts, cache_mb=args.cache_mb,
                policy=args.cache_policy, lock_shards=args.lock_shards)
            sweep_rows.append(point)
            print(f"bench_backends,{args.dataset},diskstore-contention,"
                  f"workers,{w},speedup,{point['speedup']:.3g} "
                  f"({point['global']['batches_per_s']:.3g} -> "
                  f"{point['sharded']['batches_per_s']:.3g} batches/s)")
        model = contention_model(g, contention_sweep, batch=args.batch,
                                 fanouts=fanouts, seed=args.seed)
        base = max(sweep_rows[0]["sharded"]["batches_per_s"], 1e-12)
        measured_scaling = {str(p["workers"]):
                            p["sharded"]["batches_per_s"] / base
                            for p in sweep_rows}
        for w in contention_sweep:
            print(f"bench_backends,{args.dataset},contention-model,"
                  f"workers,{w},"
                  f"measured_scaling,{measured_scaling[str(w)]:.3g},"
                  f"model_scaling,{model['scaling'][str(w)]:.3g}")
        contention = {"workers": contention_sweep, "sweep": sweep_rows,
                      "measured_scaling": measured_scaling,
                      "model": model}

    calibration = None
    if args.directio_calibrate:
        from repro.storage.engines import calibrate_directio
        calibration = calibrate_directio(store_dir, seed=args.seed)
        d = calibration["measured"]["direct"]
        print(f"bench_backends,{args.dataset},directio-calibration,"
              f"direct_mean_us,{d['mean_s'] * 1e6:.3g},"
              f"direct_io_active,{int(d['direct_io_active'])},"
              f"measured_over_model,"
              f"{calibration['measured_over_model']:.3g}")

    admission = None
    if args.admission_bench:
        admission = admission_bench()

    sweep = None
    if args.policy_sweep:
        sweep = policy_sweep(args, g, mesh, rules, store_dir)

    # sampler-family block-request locality (khop vs saint comparison);
    # loop-invariant, so computed once for the whole run
    locality = sampler_locality(g, args.sampler, steps=min(args.steps, 4),
                                batch=args.batch, fanouts=fanouts,
                                walk_length=args.walk_length)
    print(f"bench_backends,{args.dataset},{args.sampler},locality,"
          f"blocks_per_request={locality['blocks_per_request']:.3g} "
          f"block_reuse={locality['block_reuse']:.3g}")

    payload = {
        "bench": "backends",
        "dataset": args.dataset,
        "large_scale": args.large_scale,
        "steps": args.steps,
        "batch": args.batch,
        "fanouts": list(fanouts),
        "hidden": args.hidden,
        "prefetch": args.prefetch,
        "sampler": args.sampler,
        # store kinds actually benched (per-row detail lives in each
        # result's own "graph_store" field)
        "graph_store": sorted({s.store.kind for s in specs}),
        "cache_mb": args.cache_mb,
        "device_cache_rows": args.device_cache_rows,
        "edge_cache_blocks": args.edge_cache_blocks,
        "locality": locality,
        "backend_default": jax.default_backend(),
        "platform": platform.platform(),
        "results": results,
    }
    if args.wire_bench:
        wire = _wire_bench_report(results)
        payload["wire_bench"] = wire
        for p in wire["pairs"]:
            print(f"bench_backends,{args.dataset},wire_bench,"
                  f"{p['isp_row']},wire_bytes,{p['wire_bytes']},"
                  f"host_bytes_read,{p['host_bytes_read']},"
                  f"ratio,{p['wire_to_host_raw_ratio']:.3g},"
                  f"bit_identical,{int(p['loss_bit_identical'])}")
        if not wire["pairs"]:
            print("bench_backends: wire_bench found no isp/local row "
                  "pairs — check the spec list")
        elif not wire["all_bit_identical"]:
            print("bench_backends,WARNING,wire_bench,"
                  "isp loss diverged from host twin")
    if contention is not None:
        payload["contention"] = contention
    if calibration is not None:
        payload["directio_calibration"] = calibration
    if admission is not None:
        payload["devcache_admission"] = admission
    if sweep is not None:
        payload["policy_sweep"] = sweep
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
