"""Belady (optimal) cache policy: next-use computation, the OracleCache /
DeviceArrayCache schedule consumers, counter invariants across all three
policies, a hypothesis property that the oracle never evicts an entry
re-used earlier than a retained one, DiskStore raw-read replay plumbing,
and pipeline-level bit-identity of optimal-policy training vs lru."""

import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CacheTierSpec, PipelineSpec, build_pipeline
from repro.storage import (DeviceFeatureCache, DiskStore, LRUCache,
                           PinnedCache, save_graph)
from repro.storage.blockdev import FAR_NEXT_USE, OracleCache
from repro.storage.oracle import (OracleReplayer, RawDiskReader,
                                  next_use_times)

FANOUTS = (3, 2)
BATCH = 8


@pytest.fixture(scope="module")
def disk_dir(small_graph, tmp_path_factory):
    path = tmp_path_factory.mktemp("graphstore-oracle")
    save_graph(small_graph, str(path))
    return str(path)


# ---------------------------------------------------------------------------
# next_use_times
# ---------------------------------------------------------------------------

def test_next_use_times_basic():
    out = next_use_times([(0, np.array([1, 2, 3])),
                          (1, np.array([2, 4])),
                          (2, np.array([1, 2]))])
    ids0, nu0 = out[0]
    np.testing.assert_array_equal(ids0, [1, 2, 3])
    np.testing.assert_array_equal(nu0, [2, 1, FAR_NEXT_USE])
    np.testing.assert_array_equal(out[1][1], [2, FAR_NEXT_USE])
    np.testing.assert_array_equal(out[2][1],
                                  [FAR_NEXT_USE, FAR_NEXT_USE])


def test_next_use_times_matches_naive_scan():
    rng = np.random.default_rng(0)
    pairs = [(t, np.unique(rng.integers(0, 30, 12))) for t in range(6)]
    out = next_use_times(pairs)
    for t, ids in pairs:
        for j, e in enumerate(ids):
            nxt = next((u for u, uids in pairs
                        if u > t and e in uids), FAR_NEXT_USE)
            assert out[t][1][j] == nxt, (t, e)


# ---------------------------------------------------------------------------
# counter invariants: hits + misses == requests, evictions <= misses —
# for every policy's cache object, at both granularities
# ---------------------------------------------------------------------------

def _drive_block_cache(cache, trace):
    for t, blocks in enumerate(trace):
        bb = getattr(cache, "begin_batch", None)
        if bb is not None:
            sched = next_use_times(list(enumerate(trace)))
            bb(t, *sched[t])
        for b in blocks:
            cache.access(int(b))
    return cache.counters()


@pytest.mark.parametrize("make", [
    lambda: LRUCache(4),
    lambda: OracleCache(4),
])
def test_block_cache_counter_invariants(make):
    rng = np.random.default_rng(7)
    trace = [np.unique(rng.integers(0, 12, 6)) for _ in range(10)]
    requests = sum(len(b) for b in trace)
    c = _drive_block_cache(make(), trace)
    assert c["hits"] + c["misses"] == requests
    assert c["evictions"] <= c["misses"]
    assert c["misses"] > 0


def test_pinned_cache_counter_invariants(small_graph):
    pc = PinnedCache(small_graph, capacity_blocks=8)
    rng = np.random.default_rng(1)
    blocks = rng.integers(0, 64, 40)
    hits = sum(bool(pc.access(int(b))) for b in blocks)
    c = pc.counters()
    assert c["hits"] == hits
    assert c["hits"] + c["misses"] == blocks.size
    assert c["evictions"] <= c["misses"]


@pytest.mark.parametrize("policy", ["lru", "pinned", "optimal"])
def test_devcache_counter_invariants_and_bit_identity(small_graph, policy):
    g = small_graph
    dc = DeviceFeatureCache(g, rows=64, policy=policy)
    rng = np.random.default_rng(3)
    batches = [np.unique(rng.integers(0, g.num_nodes, 150))
               for _ in range(6)]
    if policy == "optimal":
        dc.oracle_feed(next_use_times(list(enumerate(batches))))
    requests = 0
    for t, ids in enumerate(batches):
        dc.oracle_begin_batch(t)
        out = np.asarray(dc.gather_rows(ids))
        np.testing.assert_array_equal(out, g.features[ids])
        requests += ids.size
    c = dc.counters()
    assert c["hits"] + c["misses"] == requests
    assert c["evictions"] <= c["misses"]
    assert c["misses"] > 0


# ---------------------------------------------------------------------------
# the Belady property: never evict an entry re-used earlier than a
# retained one (hypothesis over small synthetic traces)
# ---------------------------------------------------------------------------

@settings(max_examples=30)
@given(st.lists(st.lists(st.integers(min_value=0, max_value=15),
                         min_size=1, max_size=6),
                min_size=2, max_size=10),
       st.integers(min_value=1, max_value=5))
def test_oracle_never_evicts_earlier_reuse(trace, capacity):
    """At every eviction, the victim's scheduled next use must be >= the
    next use of every entry kept resident (two-phase protection counts:
    the current batch's entries sit at next-use == t, the minimum)."""
    batches = [np.unique(np.asarray(b, np.int64)) for b in trace]
    sched = next_use_times(list(enumerate(batches)))
    cache = OracleCache(capacity)
    for t, ids in enumerate(batches):
        cache.begin_batch(t, *sched[t])
        for b in ids:
            b = int(b)
            if cache.get(b) is None:
                evicted = cache.put_new(b, b)
                if evicted is not None:
                    ev_nu = cache._next_use_of(evicted[0])
                    kept = [cache._next_use_of(r) for r in cache._data
                            if r != b]
                    assert all(ev_nu >= k for k in kept), \
                        (t, evicted[0], ev_nu, kept)


def test_oracle_cache_beats_lru_on_scheduled_reuse():
    """The constructed case LRU gets wrong: a scan wider than capacity
    evicts the entry with the *nearest* reuse; Belady keeps it."""
    rng = np.random.default_rng(11)
    hot = np.arange(4)                       # re-used every batch
    batches = [np.unique(np.concatenate(
        [hot, rng.integers(4, 40, 8)])) for _ in range(12)]
    sched = next_use_times(list(enumerate(batches)))

    def run(cache, oracle):
        for t, ids in enumerate(batches):
            if oracle:
                cache.begin_batch(t, *sched[t])
            for b in ids:
                cache.access(int(b))
        return cache.counters()

    lru = run(LRUCache(8), False)
    opt = run(OracleCache(8), True)
    assert lru["hits"] + lru["misses"] == opt["hits"] + opt["misses"]
    assert opt["misses"] < lru["misses"]
    assert opt["evictions"] <= opt["misses"]


def test_devcache_optimal_misses_le_lru(small_graph):
    """Same skewed batch stream, same capacity: Belady never misses more
    than LRU (the sweep's per-point acceptance bar)."""
    g = small_graph
    assert g.num_nodes > 260
    # alternate between two 16-row hot sets, plus 16 one-shot cold rows
    # per batch: with 48 rows of capacity, Belady retains the *other*
    # hot set across its one-batch gap (next use == t+1) while LRU keeps
    # the freshly-stamped never-reused cold rows instead.
    a, b = np.arange(16), np.arange(16, 32)
    batches = [np.unique(np.concatenate(
        [a if t % 2 == 0 else b,
         np.arange(100 + 16 * t, 116 + 16 * t)])) for t in range(8)]

    def run(policy):
        dc = DeviceFeatureCache(g, rows=48, policy=policy,
                                pinned_fraction=0.0)
        if policy == "optimal":
            dc.oracle_feed(next_use_times(list(enumerate(batches))))
        for t, ids in enumerate(batches):
            dc.oracle_begin_batch(t)
            out = np.asarray(dc.gather_rows(ids))
            np.testing.assert_array_equal(out, g.features[ids])
        return dc.counters()

    lru, opt = run("lru"), run("optimal")
    assert lru["hits"] + lru["misses"] == opt["hits"] + opt["misses"]
    assert opt["misses"] < lru["misses"]  # strictly better here


# ---------------------------------------------------------------------------
# DiskStore plumbing: raw reads, block-id mapping, optimal policy
# ---------------------------------------------------------------------------

def test_read_indices_at_matches_resident_array(small_graph, disk_dir):
    store = DiskStore(disk_dir, cache_mb=1.0)
    try:
        full = np.asarray(small_graph.indices, np.int64)
        rng = np.random.default_rng(2)
        pos = rng.integers(0, full.size, 257)
        io0 = store.io_counters()
        got = store.read_indices_at(pos)
        np.testing.assert_array_equal(got, full[pos])
        io1 = store.io_counters()
        # raw replay reads bill no page-cache traffic
        assert io1["hits"] == io0["hits"]
        assert io1["misses"] == io0["misses"]
    finally:
        store.close()


def test_raw_disk_reader_replays_sampler_exactly(small_graph, disk_dir):
    from repro.core.sampler import replay_khop, sample_khop

    store = DiskStore(disk_dir, cache_mb=2.0)
    try:
        targets = np.random.default_rng(0).integers(
            0, store.num_nodes, BATCH).astype(np.int32)
        live = sample_khop(store, targets, FANOUTS, seed=41)
        replayed = replay_khop(RawDiskReader(store), targets, FANOUTS,
                               seed=41)
        for a, b in zip(live.hops, replayed.hops):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(live.subgraph_nodes,
                                      replayed.subgraph_nodes)
    finally:
        store.close()


def test_replay_block_ids_cover_gather_traffic(small_graph, disk_dir):
    """The replayed page-id stream must contain every block the live
    gathers actually touch (it is the oracle's view of the batch)."""
    store = DiskStore(disk_dir, cache_mb=2.0)
    try:
        rng = np.random.default_rng(9)
        nodes = np.unique(rng.integers(0, store.num_nodes, 64))
        bids = store.replay_block_ids(feature_nodes=nodes,
                                      edge_nodes=nodes,
                                      label_nodes=nodes)
        assert bids.size > 0
        assert np.array_equal(bids, np.unique(bids))
        before = store.io_counters()["misses"]
        store.gather_features(nodes)
        store.gather_edges(nodes, np.zeros((nodes.size, 1), np.int64))
        store.gather_labels(nodes)
        # replay first, then gather on a second store whose cache holds
        # exactly the replayed blocks: the gathers must be all-hits
        assert store.io_counters()["misses"] > before  # cold reads happened
    finally:
        store.close()
    store2 = DiskStore(disk_dir, cache_mb=64.0, policy="optimal")
    try:
        store2.oracle_feed({0: (bids, np.full(bids.size, 1, np.int64))})
        store2.oracle_advance(0)
        # warm exactly the replayed set via the billed path
        for b in bids:
            store2._read_range(*_key_and_range(store2, int(b)))
        m0 = store2.io_counters()["misses"]
        store2.gather_features(nodes)
        store2.gather_edges(nodes, np.zeros((nodes.size, 1), np.int64))
        store2.gather_labels(nodes)
        assert store2.io_counters()["misses"] == m0
    finally:
        store2.close()


def _key_and_range(store, bid):
    ns, blk = divmod(bid, 1 << 40)
    key = ("indptr", "indices", "features", "labels")[ns]
    return key, blk * store.block_bytes, (blk + 1) * store.block_bytes


def test_diskstore_optimal_policy_counters(disk_dir, monkeypatch):
    from repro.core import batch_targets, sample_khop

    # every read of many ranges, from the request stream: block touches
    # one range at a time less the distinct blocks of the read, i.e. the
    # re-touches of a block that adjacent ranges share
    retouches = {"lru": 0, "optimal": 0}
    read_grouped = DiskStore._read_grouped

    def counting(self, key, los, his, then):
        los, his = np.asarray(los, np.int64), np.asarray(his, np.int64)
        B = self.block_bytes
        nz = his > los
        first, last = los[nz] // B, (his[nz] - 1) // B
        touched = [np.arange(a, b + 1) for a, b in zip(first, last)]
        retouches[self.policy] += (int((last - first + 1).sum())
                                   - np.unique(np.concatenate(
                                       touched or [[]])).size)
        return read_grouped(self, key, los, his, then)

    monkeypatch.setattr(DiskStore, "_read_grouped", counting)

    def run(policy, window=4):
        store = DiskStore(disk_dir, cache_mb=0.25, policy=policy)
        try:
            if policy == "optimal":
                raw = RawDiskReader(store)

                def replay(idx):
                    t = batch_targets(store, idx, BATCH, 0)
                    tr = sample_khop(raw, t, FANOUTS, seed=idx)
                    return {"pages": store.replay_block_ids(
                        feature_nodes=tr.subgraph_nodes,
                        edge_nodes=np.unique(tr.touched_nodes),
                        label_nodes=t)}

                store.oracle_attach(OracleReplayer(
                    replay, {"pages": store.oracle_feed}, window=window))
            hops_all = []
            for i in range(8):
                store.oracle_advance(i)
                t = batch_targets(store, i, BATCH, 0)
                tr = sample_khop(store, t, FANOUTS, seed=i)
                for h in tr.hops:
                    store.gather_features(h)
                store.gather_labels(t)
                hops_all.append(tr.hops)
            return store.io_counters(), hops_all
        finally:
            store.close()

    lru, hops_lru = run("lru")
    opt, hops_opt = run("optimal")
    # identical request streams (policy changes residency, never values)
    for a, b in zip(hops_lru, hops_opt):
        for ha, hb in zip(a, b):
            np.testing.assert_array_equal(ha, hb)
    assert lru["requests"] == opt["requests"]
    # the lru store reads block-batched, looking a block two adjacent
    # ranges share up once; the optimal store looks it up per range
    assert retouches["lru"] == retouches["optimal"] > 0
    assert (lru["hits"] + lru["misses"]
            == opt["hits"] + opt["misses"] - retouches["lru"])
    assert opt["misses"] <= lru["misses"]
    assert opt["evictions"] <= opt["misses"]
    assert opt["hits"] + opt["misses"] > 0 and opt["misses"] > 0


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_cache_tier_oracle_window_validation():
    with pytest.raises(ValueError, match="oracle_window"):
        CacheTierSpec(tier="host", policy="optimal", arrays=())
    with pytest.raises(ValueError, match="oracle_window"):
        CacheTierSpec(tier="host", policy="lru", arrays=(),
                      oracle_window=4)
    with pytest.raises(ValueError, match="oracle_window"):
        CacheTierSpec(tier="host", policy="optimal", arrays=(),
                      oracle_window=-1)
    t = CacheTierSpec(tier="host", policy="optimal", arrays=(),
                      oracle_window=8)
    assert t.oracle_window == 8
    d = CacheTierSpec.device(rows=16, policy="optimal", oracle_window=4)
    assert d.oracle_window == 4 and d.policy == "optimal"


def test_oracle_window_flags_round_trip():
    import argparse

    from repro.core import add_pipeline_args, spec_from_args

    ap = argparse.ArgumentParser()
    add_pipeline_args(ap)
    args = ap.parse_args([
        "--graph-store", "disk", "--cache-policy", "optimal",
        "--cache-oracle-window", "6", "--device-cache-rows", "32",
        "--device-cache-policy", "optimal",
        "--device-cache-oracle-window", "4", "--backend", "pallas"])
    spec = spec_from_args(args)
    assert spec.host_cache_tier().policy == "optimal"
    assert spec.host_cache_tier().oracle_window == 6
    assert spec.device_cache_tier().oracle_window == 4
    # and the spec JSON round-trips the new field exactly
    assert PipelineSpec.from_json(spec.to_json()) == spec


def test_smoke_spec_twin_is_optimal_twin():
    """The CI smoke twin differs from the lru smoke only in policy and
    oracle_window — same capacities, same everything else."""
    import os
    base = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "specs")
    with open(os.path.join(base, "smoke_pallas_edgecache.json")) as f:
        lru = json.load(f)
    with open(os.path.join(base, "smoke_pallas_optimal.json")) as f:
        opt = json.load(f)
    for t in opt["cache_tiers"]:
        assert t["policy"] == "optimal" and t["oracle_window"] >= 1
        t["policy"] = "lru"
        t["oracle_window"] = 0
    for t in lru["cache_tiers"]:
        t.setdefault("oracle_window", 0)
    assert lru == opt


# ---------------------------------------------------------------------------
# pipeline-level: optimal training is bit-identical to lru
# ---------------------------------------------------------------------------

def test_pallas_optimal_training_bit_identical_to_lru(
        small_graph, host_mesh, rules, disk_dir):
    import jax
    import jax.numpy as jnp

    from repro.core import (GNNConfig, GraphSAGE, build_train_step,
                            train_loop)
    from repro.optim import adamw

    g = small_graph
    gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim, hidden=8,
                              n_classes=int(g.labels.max()) + 1,
                              fanouts=FANOUTS))
    opt = adamw(1e-3)

    def spec(policy):
        from repro.core import BackendSpec, SamplerSpec, StoreSpec
        return PipelineSpec(
            backend=BackendSpec(name="pallas"),
            sampler=SamplerSpec(family="khop", fanouts=FANOUTS),
            store=StoreSpec(kind="disk", path=disk_dir),
            cache_tiers=(
                CacheTierSpec(tier="host", policy=policy,
                              capacity_mb=0.5, arrays=(),
                              oracle_window=4 if policy == "optimal"
                              else 0),
                CacheTierSpec.device(
                    rows=48, edge_blocks=16, policy=policy,
                    oracle_window=4 if policy == "optimal" else 0)),
            batch_size=BATCH, seed=0)

    def run(policy):
        pipe = build_pipeline(spec(policy), g, mesh=host_mesh)
        try:
            step = build_train_step(pipe, gnn, opt, host_mesh, rules)
            p = gnn.init(jax.random.key(0))
            state = {"params": p, "opt": opt.init(p),
                     "step": jnp.zeros((), jnp.int32)}
            losses = []
            with host_mesh:
                state, _ = train_loop(
                    pipe, step, state, steps=4,
                    on_step=lambda i, s, m: losses.append(
                        repr(float(m["loss"]))))
            stats = pipe.stats()
        finally:
            pipe.close()
        return losses, stats

    lru_losses, lru_stats = run("lru")
    opt_losses, opt_stats = run("optimal")
    assert lru_losses == opt_losses          # repr-bit-identical
    for tier in ("devcache", "edgecache"):
        a, b = lru_stats[tier], opt_stats[tier]
        assert a["hits"] + a["misses"] == b["hits"] + b["misses"]
        assert b["misses"] <= a["misses"], tier
    assert opt_stats["oracle"]["errors"] == 0
    assert opt_stats["oracle"]["batches_replayed"] >= 4
