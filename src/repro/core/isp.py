"""Near-data subgraph generation on the mesh — the ISP architecture's
TPU-native form (DESIGN.md §2).

The paper's insight: neighbor sampling is a high-selectivity *reduction*
over a huge cold structure, so run it where the data lives and ship only
the dense result.  On a TPU mesh the cold structure (CSR edge lists +
feature table) is sharded over the ``graph`` axis; each device samples the
targets *it owns* from its local shard (`shard_map`), and only the compact
sampled-ID / gathered-feature tensors cross the ICI (a psum of the dense
result — the "subgraph over PCIe").

The anti-pattern the paper measures against (fetch raw edge-list chunks to
the host, sample there) is implemented too (``fetch_edge_chunks``): it
moves ``max_degree``-padded raw adjacency per target instead of ``fanout``
sampled IDs — the collective-byte ratio between the two paths is the
paper's 20× SSD→DRAM traffic reduction, measured in lowered HLO by
``benchmarks/bench_isp_collectives.py``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.partition import PartitionedGraph
from repro.core.sampler import DEFAULT_FANOUTS


class ISPGraph:
    """Partitioned graph resident on the mesh (leading dim = 'graph' axis)."""

    def __init__(self, pg: PartitionedGraph, mesh, *, axis: str = "data"):
        assert pg.n_shards == mesh.shape[axis], (pg.n_shards, dict(mesh.shape))
        self.mesh = mesh
        self.axis = axis
        self.n_max = pg.n_max
        self.e_max = pg.indices.shape[1]
        shard = NamedSharding(mesh, P(axis))
        dev = lambda x, s: jax.device_put(jnp.asarray(x), s)
        self.indptr = dev(pg.indptr, NamedSharding(mesh, P(axis, None)))
        self.indices = dev(pg.indices, NamedSharding(mesh, P(axis, None)))
        self.node_offset = dev(pg.node_offset.astype(np.int32), shard)
        self.features = (dev(pg.features, NamedSharding(mesh, P(axis, None,
                                                               None)))
                         if pg.features is not None else None)
        self.labels = (dev(pg.labels, NamedSharding(mesh, P(axis, None)))
                       if pg.labels is not None else None)

    # -- shard-local primitives (run inside shard_map) -----------------------

    def _local_sample(self, indptr, indices, offset, frontier, rand):
        """One hop on the local shard.  frontier/rand replicated inputs;
        non-owned targets contribute 0 so the cross-shard psum assembles
        the full subgraph (each node has exactly one owner)."""
        local = frontier - offset[0]
        owned = (local >= 0) & (local < self.n_max)
        li = jnp.clip(local, 0, self.n_max - 1)
        start = jnp.take(indptr[0], li)
        deg = jnp.take(indptr[0], li + 1) - start
        r = rand % jnp.maximum(deg[..., None], 1)
        idx = jnp.clip(start[..., None] + r, 0, self.e_max - 1)
        pick = jnp.take(indices[0], idx)
        pick = jnp.where(deg[..., None] > 0, pick,
                         frontier[..., None])          # self-loop fallback
        return jnp.where(owned[..., None], pick, 0)

    def _local_gather(self, feats, offset, ids):
        local = ids - offset[0]
        owned = (local >= 0) & (local < self.n_max)
        li = jnp.clip(local, 0, self.n_max - 1)
        rows = jnp.take(feats[0], li, axis=0)
        return jnp.where(owned[..., None], rows, 0.0)

    # -- public mesh-level ops ------------------------------------------------

    def sample_one_hop(self, frontier, fanout: int, key):
        """frontier: (...,) int32 (replicated) -> (..., fanout) int32."""
        ax = self.axis
        rand = jax.random.randint(key, frontier.shape + (fanout,), 0,
                                  2**31 - 1)

        def local(indptr, indices, offset, frontier, rand):
            mine = self._local_sample(indptr, indices, offset, frontier, rand)
            return lax.psum(mine, ax)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(ax, None), P(ax, None), P(ax), P(), P()),
            out_specs=P(), check_vma=False,
        )(self.indptr, self.indices, self.node_offset, frontier, rand)

    def sample_khop(self, targets, fanouts: Sequence[int] = DEFAULT_FANOUTS,
                    *, key):
        hops = [targets.astype(jnp.int32)]
        frontier = hops[0]
        for i, f in enumerate(fanouts):
            frontier = self.sample_one_hop(frontier, f,
                                           jax.random.fold_in(key, i))
            hops.append(frontier)
        return hops

    def gather_features(self, ids):
        """ids: (...,) int32 -> (..., F) float32 — near-data feature gather."""
        ax = self.axis

        def local(feats, offset, ids):
            return lax.psum(self._local_gather(feats, offset, ids), ax)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(ax, None, None), P(ax), P()),
            out_specs=P(), check_vma=False,
        )(self.features, self.node_offset, ids)

    def gather_labels(self, ids):
        ax = self.axis

        def local(labels, offset, ids):
            local_ids = ids - offset[0]
            owned = (local_ids >= 0) & (local_ids < self.n_max)
            li = jnp.clip(local_ids, 0, self.n_max - 1)
            vals = jnp.take(labels[0], li)
            return lax.psum(jnp.where(owned, vals, 0), ax)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(ax, None), P(ax), P()),
            out_specs=P(), check_vma=False,
        )(self.labels, self.node_offset, ids)

    def sample_and_gather(self, targets, fanouts=DEFAULT_FANOUTS, *, key):
        """Full ISP data preparation: subgraph IDs -> per-hop features.

        Returns (hop_feats, labels): the exact minibatch the GraphSAGE
        backend consumes.  Everything happens where the shard lives; the
        only cross-device bytes are the dense sampled subgraph + its
        features (the paper's step ②-⑦, Fig. 11).
        """
        hops = self.sample_khop(targets, fanouts, key=key)
        hop_feats = [self.gather_features(h) for h in hops]
        labels = self.gather_labels(hops[0])
        return hop_feats, labels

    # -- baseline comparison path (the paper's SSD(mmap) data movement) ------

    def fetch_edge_chunks(self, targets, max_degree: int):
        """Host-style raw fetch: move each target's FULL padded neighbor
        list across the mesh (the coarse block fetch of Fig. 10(a)).  Only
        used by benchmarks to measure the collective-byte ratio vs.
        ``sample_one_hop`` — the paper's 20× transfer amplification."""
        ax = self.axis

        def local(indptr, indices, offset, targets):
            local_t = targets - offset[0]
            owned = (local_t >= 0) & (local_t < self.n_max)
            li = jnp.clip(local_t, 0, self.n_max - 1)
            start = jnp.take(indptr[0], li)
            deg = jnp.take(indptr[0], li + 1) - start
            k = jnp.arange(max_degree)[None, :]
            idx = jnp.clip(start[:, None] + k, 0, self.e_max - 1)
            rows = jnp.take(indices[0], idx)
            valid = (k < deg[:, None]) & owned[:, None]
            return lax.psum(jnp.where(valid, rows, 0), ax)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(ax, None), P(ax, None), P(ax), P()),
            out_specs=P(), check_vma=False,
        )(self.indptr, self.indices, self.node_offset, targets)


def build_fused_train_step(prepare_fn, gnn, optimizer, mesh, rules):
    """Fused end-to-end step: data preparation + GraphSAGE update in ONE
    jit region, so XLA overlaps the subgraph exchange with the dense
    convolve compute where the schedule allows.  state is donated.

    ``prepare_fn(targets, key) -> (hop_feats, labels)`` must be traceable
    (the ISP mesh path and the Pallas kernel path both qualify).  The
    loader-driven generic consumer is ``core.loader.build_train_step``;
    this is the latency-optimized variant for backends whose preparation
    stage is itself jittable.
    """
    from repro.core.gnn import gnn_loss_fn

    def loss_fn(params, hop_feats, labels):
        return gnn_loss_fn(gnn, params, hop_feats, labels, mesh, rules)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state, targets, key):
        hop_feats, labels = prepare_fn(targets, key)
        (_, metrics), grads = grad_fn(state["params"], hop_feats, labels)
        new_params, new_opt, opt_metrics = optimizer.update(
            grads, state["opt"], state["params"], state["step"])
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, dict(metrics, **opt_metrics))

    return step


def build_isp_train_step(engine: ISPGraph, gnn, optimizer, mesh, rules,
                         fanouts=DEFAULT_FANOUTS):
    """Fused near-data step: ``sample_and_gather`` + update in one jit."""
    return build_fused_train_step(
        lambda targets, key: engine.sample_and_gather(targets, fanouts,
                                                      key=key),
        gnn, optimizer, mesh, rules)
