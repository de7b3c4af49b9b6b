"""Pallas TPU kernel: in-storage-style neighbor sampling (paper Alg. 1).

This is the ISP subgraph generator (Fig. 11) recast for the TPU memory
hierarchy: the big neighbor edge-list array stays in HBM (the "flash"),
held as ``(blocks, 1, BLOCK_E)`` rows (``kernels.layout``); for each
target the kernel DMAs only the two consecutive *edge-list blocks*
containing that target's neighbor list into a VMEM staging tile (the
"SSD DRAM page buffer") — the block index is computed from the
scalar-prefetched CSR offsets, exactly like the firmware's LBA->page
translation (step ③) — then gathers the S sampled entries and emits the
dense sampled-ID tensor (the "subgraph over PCIe").

HBM->VMEM traffic per target is 2 edge blocks (2*BLOCK_E*4 B) instead of
the whole edge array — the kernel-level version of the paper's 20x
transfer-amplification fix.

One kernel serves both entry points: each block is read through a
block -> slot table, which is the identity for the device-resident edge
array (``neighbor_sample``) and the cache's indirection for the HBM
edge-block cache (``neighbor_sample_cached``).

Tiling: each grid step processes ``TILE_M`` targets (grid
``(ceil(M / TILE_M),)``), staging their edge blocks into a
``(TILE_M, 1, 2*BLOCK_E)`` VMEM tile, then runs ONE vectorized
iota-compare-reduce gather over the whole tile (the vectorizable TPU
idiom for small dynamic gathers — no per-element dynamic addressing on
the VPU).

Requires max_degree <= BLOCK_E so a neighbor list spans at most two
consecutive blocks (the staged pair covers lists that straddle a block
boundary).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default targets per grid step; the dispatch-amortization knee on this
# container is 8-64, and 8 keeps the staged edge tile (8 x 2*BLOCK_E ints)
# and the one-hot gather (TILE_M x S x 2*BLOCK_E lanes) modest even for
# high-max-degree graphs.
TILE_M = 8


def edge_pad(num_edges: int, block_e: int) -> int:
    """THE edge-array pad rule: zero padding appended so a two-block
    fetch never runs off the end (at least one block past the data, at
    least two blocks total).  ``DeviceEdgeBlockCache`` derives its block
    space from this — the cached kernel's bit-identity depends on both
    sides using one definition."""
    pad = (-num_edges) % block_e + block_e
    if num_edges + pad < 2 * block_e:
        pad += block_e
    return pad


def edge_block_count(num_edges: int, block_e: int) -> int:
    """Number of ``block_e``-wide blocks in the padded edge array."""
    return (num_edges + edge_pad(num_edges, block_e)) // block_e


def _kernel(indptr_ref, slots_ref, targets_ref, rand_ref, blocks_ref,
            out_ref, staged_ref, sem, *, block_e: int, tile_m: int,
            max_block: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (tile_m, 1), 0)

    def stage(j, carry):
        off, deg, tgt = carry
        t = targets_ref[0, 0, j]
        start = indptr_ref[t]
        d = indptr_ref[t + 1] - start
        # LBA -> page translation; the clamp keeps the staged pair inside
        # the padded array and only binds for degree-0 targets at the end
        b = jnp.minimum(start // block_e, max_block)
        for h in range(2):
            # -1 = not resident; callers guarantee residency, the clamp
            # only keeps a misuse from reading out of bounds
            slot = jnp.maximum(slots_ref[b + h], 0)
            cp = pltpu.make_async_copy(
                blocks_ref.at[slot],
                staged_ref.at[j, :, pl.ds(h * block_e, block_e)], sem)
            cp.start()
            cp.wait()
        # SMEM holds scalars only: the per-target offset/degree/id are
        # assembled into (TILE_M, 1) vectors row by row
        here = row == j
        return (jnp.where(here, start - b * block_e, off),
                jnp.where(here, d, deg), jnp.where(here, t, tgt))

    zeros = jnp.zeros((tile_m, 1), jnp.int32)
    off, deg, tgt = jax.lax.fori_loop(0, tile_m, stage, (zeros,) * 3)
    blocks = staged_ref[...].reshape(tile_m, 2 * block_e)
    r = rand_ref[...] % jnp.maximum(deg, 1)                   # (TILE_M, S)
    local = off + r
    # tiled one-hot gather: picked[j, s] = blocks[j, local[j, s]]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * block_e), 2)
    onehot = local[:, :, None] == iota
    picked = jnp.sum(jnp.where(onehot, blocks[:, None, :], 0), axis=2)
    out_ref[...] = jnp.where(deg > 0, picked, tgt).astype(jnp.int32)


def _sample_call(indptr, slots, targets, rand, blocks, *, block_e: int,
                 tile_m: int, max_block: int, interpret: bool):
    """Shared dispatch: M is padded up to a multiple of ``tile_m`` (pad
    targets sample node 0, whose blocks are resident by contract, and
    are sliced off), so tile boundaries never change results.  The CSR
    offsets and the slot table are scalar-prefetched; the targets reach
    SMEM one tile per grid step, so their count is not bounded by SMEM."""
    M, S = rand.shape
    m_pad = (-M) % tile_m
    if m_pad:
        targets = jnp.pad(targets, (0, m_pad))
        rand = jnp.pad(rand, ((0, m_pad), (0, 0)))
    n_tiles = (M + m_pad) // tile_m
    kernel = functools.partial(_kernel, block_e=block_e, tile_m=tile_m,
                               max_block=max_block)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,              # indptr, slots
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((1, 1, tile_m), lambda i, *_: (i, 0, 0),
                             memory_space=pltpu.SMEM),            # targets
                pl.BlockSpec((tile_m, S), lambda i, *_: (i, 0)),   # rand
                pl.BlockSpec(memory_space=pltpu.HBM),  # blocks stay in HBM
            ],
            out_specs=pl.BlockSpec((tile_m, S), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile_m, 1, 2 * block_e), jnp.int32),  # pairs
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((M + m_pad, S), jnp.int32),
        interpret=interpret,
    )(indptr, slots, targets.reshape(n_tiles, 1, tile_m), rand, blocks)
    return out[:M]


@functools.partial(jax.jit,
                   static_argnames=("block_e", "tile_m", "interpret"))
def neighbor_sample(indptr, indices, targets, rand, *, block_e: int = 512,
                    tile_m: int = TILE_M, interpret: bool = True):
    """indptr: (N+1,) int32; indices: (E,) int32; targets: (M,) int32;
    rand: (M, S) int32.  Returns (M, S) int32.  max degree must be
    <= block_e (asserted by the ops wrapper).  The edge array is padded
    by ``edge_pad`` and viewed as ``(blocks, 1, block_e)`` rows (the
    ``kernels.layout`` row layout), every block its own slot."""
    E = indices.shape[0]
    nb = edge_block_count(E, block_e)
    blocks = jnp.pad(indices, (0, nb * block_e - E)).reshape(nb, 1, block_e)
    return _sample_call(indptr, jnp.arange(nb, dtype=jnp.int32), targets,
                        rand, blocks, block_e=block_e, tile_m=tile_m,
                        max_block=nb - 2, interpret=interpret)


# ---------------------------------------------------------------------------
# cached variant: edge blocks come from an HBM block cache via indirection
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_e", "tile_m",
                                             "max_block", "interpret"))
def neighbor_sample_cached(indptr, block_slots, targets, rand, cache, *,
                           block_e: int, max_block: int,
                           tile_m: int = TILE_M, interpret: bool = True):
    """The out-of-core-topology version of ``neighbor_sample``: the edge
    array stays *off device* and each target's two consecutive edge blocks
    are read from a ``(C, 1, block_e)`` HBM block cache via the
    ``block_slots`` (NB+1,) block-id -> slot indirection table (both
    scalar-prefetched, like the CSR offsets).  Every block a target
    dereferences must be resident (slot != -1) — the
    ``storage.devcache.DeviceEdgeBlockCache`` guarantees that by
    resolving the dispatch's planned block set first.  The staged pair's
    content equals the uncached kernel's two-block fetch, so sampled IDs
    are bit-identical."""
    return _sample_call(indptr, block_slots, targets, rand, cache,
                        block_e=block_e, tile_m=tile_m, max_block=max_block,
                        interpret=interpret)
