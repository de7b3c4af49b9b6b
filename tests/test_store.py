"""Out-of-core GraphStore: on-disk layout round-trips, block-aligned read
path, live cache counter semantics, and mem/disk bit-identity of the host
data plane (the acceptance bar for the paper's beyond-DRAM scenario)."""

import os

import numpy as np
import pytest

from repro.core import (load_dataset, kronecker_expand, make_loader,
                        rmat_graph, sample_khop)
from repro.storage import (DiskStore, InMemoryStore, MeasuredEngine, RetrySpec,
                           make_engine, open_store, save_graph)
from repro.storage.store import MANIFEST


@pytest.fixture(scope="module")
def disk_dir(small_graph, tmp_path_factory):
    path = tmp_path_factory.mktemp("graphstore")
    save_graph(small_graph, str(path))
    return str(path)


# ---------------------------------------------------------------------------
# on-disk layout
# ---------------------------------------------------------------------------

def test_save_load_roundtrip_bit_identity(small_graph, disk_dir):
    g = small_graph
    st = DiskStore(disk_dir)
    g2 = st.to_csr()
    np.testing.assert_array_equal(g2.indptr, g.indptr)
    np.testing.assert_array_equal(g2.indices, g.indices)
    np.testing.assert_array_equal(g2.features, g.features)
    np.testing.assert_array_equal(g2.labels, g.labels)
    assert g2.indices.dtype == np.int32
    assert g2.features.dtype == np.float32
    g2.validate()
    st.close()


def test_layout_is_block_aligned(disk_dir):
    st = DiskStore(disk_dir)
    for key, meta in st.manifest["arrays"].items():
        size = os.path.getsize(os.path.join(disk_dir, meta["file"]))
        assert size % st.block_bytes == 0, key
        assert size >= meta["nbytes"]
    st.close()


def test_edge_byte_range_agreement(small_graph, disk_dir):
    """The store's on-disk byte extents (int32 entries) agree with the
    graph's ``edge_byte_range`` at the same entry width, and reading a
    node's neighbor list touches exactly those blocks."""
    g = small_graph
    entry = 4                                   # on-disk int32 entries
    for u in (0, 7, int(np.argmax(g.degrees()))):
        st = DiskStore(disk_dir, cache_blocks=4)    # cold cache per node
        assert st.edge_byte_range(u) == g.edge_byte_range(u, entry)
        lo, hi = st.edge_byte_range(u)
        want_blocks = max(hi - 1, lo) // st.block_bytes - lo // st.block_bytes + 1
        nbrs = st.neighbors(u)
        np.testing.assert_array_equal(nbrs, g.neighbors(u))
        if hi > lo:                             # cold cache: every block
            assert st.io_counters()["block_fetches"] == want_blocks
        st.close()


def test_store_without_features_rejects_gather(tmp_path):
    g = rmat_graph(64, 256, seed=0)             # no features attached
    save_graph(g, str(tmp_path))
    st = DiskStore(str(tmp_path))
    with pytest.raises(ValueError):
        st.gather_features(np.arange(4))
    st.close()


# ---------------------------------------------------------------------------
# live cache semantics
# ---------------------------------------------------------------------------

def test_cache_counters_under_forced_eviction(small_graph, disk_dir):
    """A working set larger than the cache must evict and re-miss; the
    counters must stay consistent (hits + misses = lookups, every miss is
    one block fetch)."""
    st = DiskStore(disk_dir, cache_blocks=8)
    # sweep all feature rows twice: working set >> 8 blocks, so the second
    # pass cannot be served from cache
    for _ in range(2):
        for u in range(0, st.num_nodes, 50):
            st.gather_features(np.array([u]))
    io = st.io_counters()
    assert io["misses"] > 0
    assert io["evictions"] > 0
    assert io["block_fetches"] == io["misses"]
    assert io["hits"] + io["misses"] >= io["requests"]
    # second sweep re-missed: far more fetches than unique blocks touched
    unique_blocks = len({(u * st.feat_dim * 4) // st.block_bytes
                         for u in range(0, st.num_nodes, 50)})
    assert io["misses"] > unique_blocks
    st.close()


def test_cache_hit_path_reuses_blocks(disk_dir):
    st = DiskStore(disk_dir, cache_mb=4)
    st.neighbors(3)
    before = st.io_counters()
    st.neighbors(3)                              # same chunk: pure hits
    after = st.io_counters()
    assert after["block_fetches"] == before["block_fetches"]
    assert after["hits"] > before["hits"]
    st.close()


def test_pinned_policy_serves_hot_blocks(small_graph, disk_dir):
    g = small_graph
    st = DiskStore(disk_dir, cache_mb=1, policy="pinned")
    staged = st.io_counters()["block_fetches"]
    assert staged > 0                            # scratchpad pre-staged
    hub = int(np.argmax(g.degrees()))
    before = st.io_counters()
    np.testing.assert_array_equal(st.neighbors(hub), g.neighbors(hub))
    after = st.io_counters()
    assert after["block_fetches"] == before["block_fetches"]  # pinned hit
    assert after["hits"] > before["hits"]
    st.close()


# ---------------------------------------------------------------------------
# block-batched reads
# ---------------------------------------------------------------------------

def _feature_blocks(st, ids):
    """The distinct feature-table blocks rows ``ids`` cover, ascending."""
    row = st.feat_dim * 4
    lo = np.asarray(ids, np.int64) * row
    return np.unique(np.concatenate([np.arange(a, b + 1) for a, b in
                                     zip(lo // st.block_bytes,
                                         (lo + row - 1) // st.block_bytes)]))


@pytest.mark.parametrize("cache_blocks, below_a_batch",
                         [(64, True), (2048, False)])
def test_batched_reads_match_a_sequential_lru_replay(small_graph, disk_dir,
                                                     cache_blocks,
                                                     below_a_batch):
    """Rows stay bit-identical, and the counters are those of an LRU that
    touches each batch's distinct blocks one at a time in ascending
    order: below one batch's blocks (64) and above the table (2048)."""
    from repro.storage import LRUCache
    g = small_graph
    st = DiskStore(disk_dir, cache_blocks=cache_blocks, lock_shards=1,
                   io_threads=1)
    replay = LRUCache(cache_blocks)
    ns = st._ns["features"] << 40
    rng = np.random.default_rng(7)
    requests = 0
    try:
        for _ in range(6):
            ids = rng.choice(g.num_nodes, 200, replace=False)
            blocks = _feature_blocks(st, ids)
            assert (cache_blocks < blocks.size) == below_a_batch
            np.testing.assert_array_equal(st.gather_features(ids),
                                          g.features[ids])
            for b in blocks:
                replay.access(ns + int(b))
            requests += ids.size
        io = st.io_counters()
    finally:
        st.close()
    assert io["requests"] == requests
    assert io["misses"] == replay.misses
    assert io["hits"] == replay.hits
    assert io["evictions"] == replay.evictions
    assert io["block_fetches"] == replay.misses
    assert io["bytes_fetched"] == replay.misses * st.block_bytes


@pytest.mark.parametrize("block_fails_again", [False, True])
def test_short_run_read_falls_back_to_per_block_fetch(small_graph, disk_dir,
                                                      monkeypatch,
                                                      block_fails_again):
    """A coalesced read that comes back short is billed as one short read
    and one retry, as a block read would be, and is read again block by
    block through ``_fetch``; a block that is short once more there is
    retried by the policy and billed once more."""
    g = small_graph
    st = DiskStore(disk_dir, cache_blocks=1024, io_threads=1,
                   retry=RetrySpec(backoff_s=0.0))
    B = st.block_bytes
    real = os.pread
    state = {"run": None, "block": 0}

    def flaky(fd, n, off):
        data = real(fd, n, off)
        if n > B and state["run"] is None:         # the first run read
            state["run"] = (off // B, n // B)
            return data[:n - 100]
        if block_fails_again and n == B and state["block"] == 0 \
                and state["run"] is not None \
                and off // B == state["run"][0] + 1:
            state["block"] = 1                     # its 2nd block, once
            return data[:B // 2]
        return data

    monkeypatch.setattr(os, "pread", flaky)
    ids = np.arange(0, 40)                         # one run of blocks
    try:
        np.testing.assert_array_equal(st.gather_features(ids),
                                      g.features[ids])
        io = st.io_counters()
    finally:
        st.close()
    first, n = state["run"]
    again = int(block_fails_again)
    assert state["block"] == again and n == io["block_fetches"] > 1
    assert io["short_reads"] == 1 + again and io["retries"] == 1 + again
    assert io["io_errors"] == io["corrupt_blocks"] == io["timeouts"] == 0
    # the run's pread, then one per block, plus the retried block's
    assert io["preads"] == 1 + n + again
    assert io["misses"] == io["block_fetches"] == n


def test_preads_count_the_runs_of_missed_blocks(small_graph, disk_dir):
    """One pread per run of consecutive missed blocks: never more than
    the blocks fetched, and exactly the runs for sorted contiguous rows."""
    g = small_graph
    st = DiskStore(disk_dir, cache_blocks=2048, io_threads=1)
    try:
        spans = [np.arange(0, 30), np.arange(200, 260), np.arange(700, 701)]
        ids = np.concatenate(spans)
        np.testing.assert_array_equal(st.gather_features(ids),
                                      g.features[ids])
        io = st.io_counters()
        assert io["preads"] == len(spans)
        assert io["block_fetches"] == _feature_blocks(st, ids).size
        # a second read of the same rows hits: no blocks, no preads
        st.gather_features(ids)
        assert st.io_counters()["preads"] == len(spans)
        # a single range over fresh blocks is one run too
        row = st.feat_dim
        before = st.io_counters()
        np.testing.assert_array_equal(
            st._read_array("features", 300 * row, 330 * row),
            g.features[300:330].reshape(-1))
        io = st.io_counters()
        assert io["preads"] - before["preads"] == 1
        assert io["block_fetches"] - before["block_fetches"] == \
            _feature_blocks(st, np.arange(300, 330)).size > 1
        rng = np.random.default_rng(3)
        for _ in range(4):
            st.gather_features(rng.choice(g.num_nodes, 100, replace=False))
            io = st.io_counters()
            assert 0 < io["preads"] <= io["block_fetches"]
    finally:
        st.close()


def test_a_claimed_slot_is_a_miss_until_filled(small_graph, disk_dir):
    """A block one batched read has claimed but not yet filled is a miss
    to another, who reads it and fills the slot; the first reader's own
    fill then leaves the slot as it is."""
    from repro.storage.blockdev import RESERVED
    g = small_graph
    st = DiskStore(disk_dir, cache_blocks=8, lock_shards=1, io_threads=1)
    shard = st._shards[0]
    bid = st._ns["features"] << 40                 # features block 0
    try:
        hit_at, _, miss_at, _ = shard.lookup_run([bid])
        assert (hit_at, miss_at) == ([], [0])
        assert shard._od[bid] is RESERVED
        np.testing.assert_array_equal(
            st._read_array("features", 0, st.feat_dim), g.features[0])
        io = st.io_counters()
        assert io["misses"] == 2 and io["hits"] == 0   # claim + reader
        assert io["block_fetches"] == 1                # the reader's read
        filled = shard._od[bid]
        assert filled is not RESERVED and len(filled) == st.block_bytes
        shard.fill([bid], [b"stale"])
        assert shard._od[bid] is filled
    finally:
        st.close()


def test_concurrent_batched_readers_share_the_cache(small_graph, disk_dir):
    """Many threads read one store at once, many ranges through the
    pread pool and single ranges, with the interpreter switching
    threads as often as it can: rows stay bit-identical, every miss is
    one fetch, each thread's context is billed exactly its own reads,
    and no claimed slot is left unfilled."""
    import sys
    import threading

    from repro.storage.blockdev import RESERVED
    g = small_graph
    st = DiskStore(disk_dir, cache_blocks=32, io_threads=4)
    errors, ctxs = [], []

    def reader(seed):
        rng = np.random.default_rng(seed)
        ctx = st.make_io_context()
        ctxs.append(ctx)
        try:
            with st.io_attribution(ctx):
                for _ in range(10):
                    # 256 rows (151 blocks) over a 32-block cache: the
                    # threads keep claiming and reading the same blocks
                    ids = rng.choice(256, 48, replace=False)
                    np.testing.assert_array_equal(st.gather_features(ids),
                                                  g.features[ids])
                    for u in rng.choice(256, 4).tolist():
                        np.testing.assert_array_equal(
                            st._read_array("features", u * st.feat_dim,
                                           (u + 1) * st.feat_dim),
                            g.features[u])
        except Exception as e:          # reported below, on the main thread
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reader, args=(s,))
                   for s in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
        io = st.io_counters()
        resident = [v for sh in st._shards for v in sh._od.values()]
        st.close()
    assert not errors, errors[0]
    assert io["block_fetches"] == io["misses"] > 0
    assert io["bytes_fetched"] == io["block_fetches"] * st.block_bytes
    assert io["requests"] == 12 * 10 * (48 + 4)
    for key in ("requests", "block_fetches", "misses", "preads"):
        assert sum(c.counters()[key] for c in ctxs) == io[key], key
    assert not any(v is RESERVED for v in resident)


# ---------------------------------------------------------------------------
# sampling + host data plane through the store
# ---------------------------------------------------------------------------

def test_sampler_mem_disk_bit_identity(small_graph, disk_dir):
    g = small_graph
    st = DiskStore(disk_dir, cache_mb=0.25)
    targets = np.arange(32)
    a = sample_khop(g, targets, (5, 3), seed=11)
    b = sample_khop(st, targets, (5, 3), seed=11)
    for t, (ha, hb) in enumerate(zip(a.hops, b.hops)):
        np.testing.assert_array_equal(ha, hb, err_msg=f"hop {t}")
    np.testing.assert_array_equal(a.touched_nodes, b.touched_nodes)
    np.testing.assert_array_equal(a.subgraph_nodes, b.subgraph_nodes)
    assert a.io is None                          # raw arrays: nothing issued
    assert b.io is not None and b.io["requests"] > 0
    st.close()


def test_inmemory_store_matches_raw_graph(small_graph):
    g = small_graph
    st = InMemoryStore(g)
    a = sample_khop(g, np.arange(16), (4, 2), seed=3)
    b = sample_khop(st, np.arange(16), (4, 2), seed=3)
    for ha, hb in zip(a.hops, b.hops):
        np.testing.assert_array_equal(ha, hb)
    assert b.io == st.io_counters()              # all zeros, but recorded
    np.testing.assert_array_equal(st.gather_features(np.arange(8)),
                                  g.features[:8])


def test_host_loader_mem_disk_bit_identity(small_graph, disk_dir):
    """The acceptance bar: at equal seeds the disk-backed host loader
    produces bit-identical minibatches to the in-memory one, while its
    page cache records real misses."""
    g = small_graph
    mem = make_loader("host", g, batch_size=8, fanouts=(3, 2), seed=0)
    disk = make_loader("host", None, batch_size=8, fanouts=(3, 2), seed=0,
                       store=DiskStore(disk_dir, cache_mb=0.25))
    try:
        for i in range(3):
            a, b = mem.get_batch(i), disk.get_batch(i)
            np.testing.assert_array_equal(a.targets, b.targets)
            for x, y in zip(a.hop_ids, b.hop_ids):
                np.testing.assert_array_equal(x, y)
            for x, y in zip(a.hop_feats, b.hop_feats):
                np.testing.assert_array_equal(x, y)
            np.testing.assert_array_equal(a.labels, b.labels)
            assert b.trace.io is not None
        stats = disk.stats()
        assert stats["store"]["misses"] > 0
    finally:
        mem.close()
        disk.close()


def test_measured_engine_reports_real_io(small_graph, disk_dir):
    g = small_graph
    st = DiskStore(disk_dir, cache_mb=0.25)
    eng = make_engine("mmap", g, measured=True, store=st)
    assert isinstance(eng, MeasuredEngine)
    trace = sample_khop(st, np.arange(16), (4, 2), seed=5)
    cost = eng.batch_cost(trace)
    assert cost.time_s > 0                       # simulated model intact
    assert cost.meta["measured"]["block_fetches"] == \
        trace.io["block_fetches"]
    rep = eng.report()
    assert rep["measured_totals"]["requests"] == trace.io["requests"]
    assert rep["store"]["kind"] == "disk"
    st.close()


def test_open_store_registry(small_graph, tmp_path):
    st = open_store("mem", g=small_graph)
    assert isinstance(st, InMemoryStore)
    st2 = open_store("disk", g=small_graph, path=str(tmp_path))
    assert isinstance(st2, DiskStore)
    assert os.path.exists(os.path.join(str(tmp_path), MANIFEST))
    assert st2.num_edges == small_graph.num_edges
    st2.close()
    with pytest.raises(KeyError):
        open_store("tape", g=small_graph)


def test_open_store_rejects_stale_layout(small_graph, tmp_path):
    """Reusing a --store-dir that holds a *different* graph must fail
    loudly instead of silently training on stale data."""
    open_store("disk", g=small_graph, path=str(tmp_path)).close()
    other = rmat_graph(32, 128, seed=1)
    with pytest.raises(ValueError, match="stale"):
        open_store("disk", g=other, path=str(tmp_path))
    # same graph: reuse is fine
    open_store("disk", g=small_graph, path=str(tmp_path)).close()


# ---------------------------------------------------------------------------
# kronecker_expand chunked build (peak-memory fix)
# ---------------------------------------------------------------------------

def test_kronecker_chunked_bit_identical(tmp_path):
    g = rmat_graph(256, 2048, seed=9)
    base = kronecker_expand(g, 4, seed=1, edge_keep=0.6, chunk_pairs=1)
    for chunk in (2, 3, 100):
        other = kronecker_expand(g, 4, seed=1, edge_keep=0.6,
                                 chunk_pairs=chunk)
        np.testing.assert_array_equal(base.indptr, other.indptr)
        np.testing.assert_array_equal(base.indices, other.indices)
    spilled = kronecker_expand(g, 4, seed=1, edge_keep=0.6, chunk_pairs=2,
                               spill_dir=str(tmp_path / "spill"))
    np.testing.assert_array_equal(base.indptr, spilled.indptr)
    np.testing.assert_array_equal(base.indices, spilled.indices)
    assert not os.listdir(str(tmp_path / "spill"))   # spill files cleaned
