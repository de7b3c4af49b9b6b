"""Out-of-core topology: the device edge-block cache + cached sampling
kernel.  The acceptance bar is bit-identity — pallas training through an
HBM edge-block cache smaller than the edge array must match the
full-edge-array-upload path exactly, with both cache counter families
reported in the batch trace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BackendSpec, CacheTierSpec, GNNConfig, GraphSAGE,
                        PipelineSpec, SamplerSpec, StoreSpec, build_pipeline,
                        build_train_step, make_loader, train_loop)
from repro.kernels import ops
from repro.optim import adamw
from repro.storage import (DeviceEdgeBlockCache, DiskStore, edge_block_count,
                           save_graph)

FANOUTS = (3, 2)
BATCH = 8


@pytest.fixture(scope="module")
def disk_dir(small_graph, tmp_path_factory):
    path = tmp_path_factory.mktemp("graphstore-edge")
    save_graph(small_graph, str(path))
    return str(path)


def _edge_tier(blocks, rows=0, policy="lru"):
    arrays = (("features",) if rows else ()) + ("topology",)
    return CacheTierSpec(tier="device", rows=rows, edge_blocks=blocks,
                         policy=policy, arrays=arrays)


# ---------------------------------------------------------------------------
# DeviceEdgeBlockCache core
# ---------------------------------------------------------------------------

def test_block_contents_match_padded_edge_array(small_graph):
    g = small_graph
    block_e = ops.edge_block_size(int(g.degrees().max()))
    nb = edge_block_count(g.num_edges, block_e)
    dc = DeviceEdgeBlockCache(g, indptr=g.indptr, block_e=block_e,
                              blocks=8)
    want_all = np.zeros(nb * block_e, np.int32)
    want_all[:g.num_edges] = g.indices
    for blocks in ([0, 1], [nb - 2, nb - 1], [3, 4, 5]):
        dc.resolve(np.asarray(blocks))
        table = np.asarray(dc.table)[:, 0]      # (C, 1, block_e) rows
        slots = np.asarray(dc.slot_of)
        for b in blocks:
            np.testing.assert_array_equal(
                table[slots[b]], want_all[b * block_e:(b + 1) * block_e],
                err_msg=f"block {b}")


def test_plan_fits_budget_and_covers_padding(small_graph):
    g = small_graph
    block_e = ops.edge_block_size(int(g.degrees().max()))
    dc = DeviceEdgeBlockCache(g, indptr=g.indptr, block_e=block_e,
                              blocks=5)
    rng = np.random.default_rng(0)
    targets = rng.integers(0, g.num_nodes, 64)
    chunks = dc.plan(targets)
    covered = 0
    for sl, blocks in chunks:
        nonpinned = np.count_nonzero(~dc._pinned_mask[blocks])
        assert nonpinned <= dc._lru_capacity
        assert 0 in blocks and 1 in blocks       # tile-padding pair
        seg = targets[sl]
        b0 = np.minimum(g.indptr[seg] // block_e, dc.max_block)
        assert set(b0) | set(b0 + 1) <= set(blocks.tolist())
        covered += seg.size
    assert covered == targets.size


def test_too_small_edge_cache_raises(small_graph):
    g = small_graph
    block_e = ops.edge_block_size(int(g.degrees().max()))
    with pytest.raises(ValueError, match="4 non-pinned"):
        DeviceEdgeBlockCache(g, indptr=g.indptr, block_e=block_e, blocks=3)
    with pytest.raises(ValueError, match="4 non-pinned"):
        DeviceEdgeBlockCache(g, indptr=g.indptr, block_e=block_e, blocks=6,
                             policy="pinned", pinned_fraction=0.5)


# ---------------------------------------------------------------------------
# cached sampling through the loader: the acceptance bar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,blocks", [("lru", 16), ("pinned", 16),
                                           ("lru", 6)])
def test_pallas_edgecached_bit_identity(small_graph, policy, blocks):
    """pallas@edgecache == pallas@full-upload, bit for bit — including a
    cache so small the planner must split every hop into chunks."""
    g = small_graph
    full = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                       seed=0)
    cached = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                         seed=0,
                         device_cache=_edge_tier(blocks, policy=policy))
    try:
        for i in range(3):
            a, b = full.get_batch(i), cached.get_batch(i)
            np.testing.assert_array_equal(a.targets, b.targets)
            for t, (x, y) in enumerate(zip(a.hop_ids, b.hop_ids)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                              err_msg=f"hop {t}")
            for t, (x, y) in enumerate(zip(a.hop_feats, b.hop_feats)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                              err_msg=f"hop {t}")
            np.testing.assert_array_equal(np.asarray(a.labels),
                                          np.asarray(b.labels))
            ec = b.trace.io["edgecache"]
            assert ec["hits"] + ec["misses"] > 0
        stats = cached.stats()["edgecache"]
        assert stats["capacity_rows"] == blocks
        assert stats["misses"] > 0
    finally:
        full.close()
        cached.close()


def test_pallas_edgecached_loss_trajectory_bit_identical(small_graph):
    g = small_graph

    def trajectory(loader):
        gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim, hidden=16,
                                  n_classes=int(g.labels.max()) + 1,
                                  fanouts=FANOUTS))
        opt = adamw(3e-3)
        step = build_train_step(loader, gnn, opt)
        p = gnn.init(jax.random.key(0))
        state = {"params": p, "opt": opt.init(p),
                 "step": jnp.zeros((), jnp.int32)}
        losses = []
        train_loop(loader, step, state, steps=3,
                   on_step=lambda i, s, m: losses.append(
                       np.asarray(m["loss"])))
        return losses

    full = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                       seed=0)
    cached = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                         seed=0, device_cache=_edge_tier(16))
    try:
        la = trajectory(full)
        lb = trajectory(cached)
    finally:
        full.close()
        cached.close()
    np.testing.assert_array_equal(la, lb)


def test_combined_feature_and_topology_cache(small_graph, disk_dir):
    """One device tier covering both array families over a real DiskStore:
    every miss family is real paged disk I/O, and both counter blocks
    ride in the trace next to the host page-cache counters."""
    g = small_graph
    spec = PipelineSpec(
        backend=BackendSpec(name="pallas"),
        sampler=SamplerSpec(fanouts=FANOUTS),
        store=StoreSpec(kind="disk", path=disk_dir),
        cache_tiers=(
            CacheTierSpec(tier="host", capacity_mb=0.25, arrays=()),
            CacheTierSpec(tier="device", rows=24, edge_blocks=16,
                          arrays=("features", "topology"))),
        batch_size=BATCH, seed=0)
    full = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                       seed=0)
    pipe = build_pipeline(spec, g)
    try:
        for i in range(2):
            a, b = full.get_batch(i), pipe.get_batch(i)
            for x, y in zip(a.hop_feats, b.hop_feats):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            io = b.trace.io
            assert io["devcache"]["misses"] > 0
            assert io["edgecache"]["misses"] > 0
            assert io["block_fetches"] > 0       # host page-cache counters
    finally:
        full.close()
        pipe.close()


def test_edgecache_misses_are_real_paged_reads(small_graph, disk_dir):
    g = small_graph
    st = DiskStore(disk_dir, cache_mb=0.25)
    block_e = ops.edge_block_size(int(g.degrees().max()))
    dc = DeviceEdgeBlockCache(st, indptr=g.indptr, block_e=block_e,
                              blocks=8)
    io0 = st.io_counters()
    dc.resolve(np.arange(6))
    io1 = st.io_counters()
    assert io1["block_fetches"] > io0["block_fetches"]
    # contents still exact through the paged path
    table = np.asarray(dc.table)[:, 0]          # (C, 1, block_e) rows
    slots = np.asarray(dc.slot_of)
    np.testing.assert_array_equal(table[slots[0]],
                                  np.pad(g.indices[:block_e],
                                         (0, max(0, block_e - g.num_edges))
                                         )[:block_e])
    st.close()


def test_edgecached_under_prefetch_bit_identical(small_graph):
    """Edge-block admission in the prefetch worker must not change
    results."""
    g = small_graph
    sync = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                       seed=0, device_cache=_edge_tier(16))
    pre = make_loader("pallas", g, batch_size=BATCH, fanouts=FANOUTS,
                      seed=0, prefetch=2, device_cache=_edge_tier(16))
    try:
        for i in range(3):
            a, b = sync.get_batch(i), pre.get_batch(i)
            for x, y in zip(a.hop_feats, b.hop_feats):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    finally:
        sync.close()
        pre.close()


def test_epoch_counters_cover_edgecache(small_graph):
    loader = make_loader("pallas", small_graph, batch_size=BATCH,
                         fanouts=FANOUTS, seed=0,
                         device_cache=_edge_tier(16))
    try:
        loader.get_batch(0)
        loader.start_epoch()
        loader.get_batch(1)
        s = loader.stats()
        assert s["edgecache_epoch"]["hits"] + \
            s["edgecache_epoch"]["misses"] > 0
        assert s["edgecache_epoch"]["misses"] <= s["edgecache"]["misses"]
    finally:
        loader.close()
