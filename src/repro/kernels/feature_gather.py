"""Pallas TPU kernel: tiled feature gather (+ optional fanout-mean).

The GNN data-preparation hot spot (paper Fig. 1 steps ②-③): gather sampled
neighbors' feature rows from the (possibly huge) feature table, and for the
aggregate step mean-reduce them over the fanout.

TPU adaptation (DESIGN.md §2/§5): the feature table stays in HBM (the
"flash array"), held in the ``(N, 1, Wp)`` row layout of
``kernels.layout`` so that one row is one aligned DMA; the sampled IDs
reach SMEM one tile per grid step; each grid step stages a *tile* of
``TILE_M`` rows into a VMEM scratch buffer with per-row async copies (the
firmware's LBA->page DMA, step ③) and then operates on the whole
``(TILE_M, F)`` block at once.  Only the requested rows ever cross.

Three entry points share the kernel:

* ``feature_gather_rows``: grid ``(ceil(R / TILE_M), 1)`` — a flat row
  gather, one pallas_call per hop tensor.
* ``feature_gather_mean``: grid ``(ceil(M / TILE_M), K)`` — the output
  tile is revisited across the inner fanout dim and accumulates the mean;
  no ``(M, K, F)`` intermediate ever materializes ("ship the reduction,
  not the raw rows", the paper's ISP principle).
* ``feature_gather_cached``: the row gather through a node-id -> slot
  table into an HBM row cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default rows staged per grid step: 64 keeps the VMEM tile (64 x Wp
# floats) small while making the grid ~64x shorter.
TILE_ROWS = 64


def _kernel(*refs, tile_m: int, K: int, width: int, indirect: bool):
    if indirect:
        slots_ref, ids_ref, table_ref, out_ref, rows_ref, sem = refs
    else:
        ids_ref, table_ref, out_ref, rows_ref, sem = refs

    def stage(j, carry):
        # per-row DMA: HBM table row -> VMEM tile slot j (step ③); the
        # cached gather first looks the node id up in the scalar-
        # prefetched slot table (-1 = not resident; callers guarantee
        # residency, the clamp only keeps a misuse in bounds)
        row = ids_ref[0, 0, j]
        if indirect:
            row = jnp.maximum(slots_ref[row], 0)
        cp = pltpu.make_async_copy(table_ref.at[row], rows_ref.at[j], sem)
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, tile_m, stage, 0)
    rows = rows_ref[...].reshape(tile_m, -1)[:, :width].astype(out_ref.dtype)
    if K == 1:
        out_ref[...] = rows
        return

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += rows / K


def _gather_call(table, ids2d, *, width: int, tile_m: int, interpret: bool,
                 slot_of=None):
    """table: (N, 1, Wp) row layout; ids2d: (M, K) int32, M a multiple of
    tile_m -> (M, width) float32 fanout-mean of gathered rows.  The ids
    reach SMEM one (tile, fanout slot) block per grid step, so their
    count is not bounded by SMEM; ``slot_of`` (scalar-prefetched)
    switches on the cache indirection."""
    M, K = ids2d.shape
    n_tiles = M // tile_m
    ids = ids2d.T.reshape(K * n_tiles, 1, tile_m)
    scalars = () if slot_of is None else (slot_of.astype(jnp.int32),)
    kernel = functools.partial(_kernel, tile_m=tile_m, K=K, width=width,
                               indirect=slot_of is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_tiles, K),
            in_specs=[
                pl.BlockSpec((1, 1, tile_m),
                             lambda i, k, *_: (k * n_tiles + i, 0, 0),
                             memory_space=pltpu.SMEM),           # ids
                pl.BlockSpec(memory_space=pltpu.HBM),  # table stays in HBM
            ],
            out_specs=pl.BlockSpec((tile_m, width), lambda i, k, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile_m,) + table.shape[1:], table.dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((M, width), jnp.float32),
        interpret=interpret,
    )(*scalars, ids, table)


@functools.partial(jax.jit, static_argnames=("width", "tile_m", "interpret"))
def feature_gather_mean(table, ids, *, width: int, tile_m: int = TILE_ROWS,
                        interpret: bool = True):
    """table: (N, 1, Wp) row layout of an (N, width) table; ids: (M, K)
    int32 -> (M, width) mean of gathered rows.

    M is padded up to a multiple of ``tile_m`` (pad rows gather row 0 and
    are sliced off), so tile boundaries never change results."""
    M, K = ids.shape
    pad = (-M) % tile_m
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
    out = _gather_call(table, ids.astype(jnp.int32), width=width,
                       tile_m=tile_m, interpret=interpret)
    return out[:M].astype(table.dtype)


@functools.partial(jax.jit, static_argnames=("width", "tile_m", "interpret"))
def feature_gather_rows(table, ids, *, width: int, tile_m: int = TILE_ROWS,
                        interpret: bool = True):
    """table: (N, 1, Wp) row layout; ids: (R,) int32 -> (R, width) row
    gather — the K=1 case, one pallas_call for the whole hop tensor."""
    R = ids.shape[0]
    pad = (-R) % tile_m
    if pad:
        ids = jnp.pad(ids, (0, pad))
    out = _gather_call(table, ids.astype(jnp.int32)[:, None], width=width,
                       tile_m=tile_m, interpret=interpret)
    return out[:R].astype(table.dtype)


# ---------------------------------------------------------------------------
# cached gather: node-id -> slot indirection into an HBM-resident row cache
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("width", "tile_m", "interpret"))
def feature_gather_cached(cache, slot_of, ids, *, width: int,
                          tile_m: int = TILE_ROWS, interpret: bool = True):
    """cache: (C, 1, Wp) HBM-resident row cache (row layout); slot_of:
    (N+1,) int32 node-id -> slot indirection table; ids: (R,) int32 node
    ids, all resident.  Returns (R, width) float32.  R is padded up to a
    tile multiple with edge ids (repeats of the last id — resident by
    contract), so padding never dereferences an unmapped slot."""
    R = ids.shape[0]
    pad = (-R) % tile_m
    if pad:
        ids = jnp.pad(ids, (0, pad), mode="edge")
    out = _gather_call(cache, ids.astype(jnp.int32)[:, None], width=width,
                       tile_m=tile_m, interpret=interpret, slot_of=slot_of)
    return out[:R]
