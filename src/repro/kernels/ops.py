"""Public jit'd wrappers for the Pallas kernels.

Each op validates/pads shapes, picks hardware-aligned block sizes, and
dispatches to the Pallas kernel — compiled on the TPU, in interpret mode
on the CPU (where the tests run), and refused on any other backend.
``use_kernels(False)`` swaps in the jnp oracles, which is what the
512-device dry-run lowers (kernels are a per-device compute detail, not
a sharding one).

Feature tables and both HBM caches are passed in the ``(n, 1, Wp)`` row
layout of ``kernels.layout``; ``width`` is the logical row width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pl
from repro.kernels.flash_attention import flash_attention as _flash_pl
from repro.kernels.feature_gather import feature_gather_cached as _cached_pl
from repro.kernels.feature_gather import feature_gather_mean as _gather_pl
from repro.kernels.feature_gather import feature_gather_rows as _rows_pl
from repro.kernels.layout import from_rows
from repro.kernels.neighbor_sample import neighbor_sample as _sample_pl
from repro.kernels.neighbor_sample import \
    neighbor_sample_cached as _sample_cached_pl
from repro.kernels.ssd_chunk_scan import ssd_chunk_scan as _ssd_pl

_ENABLED = True


def use_kernels(enabled: bool) -> None:
    global _ENABLED
    _ENABLED = enabled


def kernels_enabled() -> bool:
    return _ENABLED


def _interpret() -> bool:
    """Compile on the TPU, interpret on the CPU; no other backend has a
    Pallas TPU kernel path, and none falls back silently."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels compile on 'tpu' and are "
                       f"interpreted on 'cpu'; backend {backend!r} has "
                       "neither")


def feature_gather_mean(table, ids, *, width: int):
    """(N, 1, Wp) row table, (M, K) int32 -> (M, width) fanout-mean of
    gathered rows."""
    if not _ENABLED:
        return ref.feature_gather_mean(from_rows(table, width), ids)
    return _gather_pl(table, ids, width=width, interpret=_interpret())


def edge_block_size(max_degree: int) -> int:
    """The kernels' shared edge-block width: 128-aligned and >= the max
    neighbor-list length, so any list spans at most two blocks."""
    return max(128, int(-(-max_degree // 128) * 128))


def neighbor_sample(indptr, indices, targets, rand, *, max_degree: int):
    """CSR fanout sample.  block_e sized from max_degree (128-aligned)."""
    if not _ENABLED:
        return ref.neighbor_sample(indptr, indices, targets, rand)
    return _sample_pl(indptr.astype(jnp.int32), indices.astype(jnp.int32),
                      targets.astype(jnp.int32), rand.astype(jnp.int32),
                      block_e=edge_block_size(max_degree),
                      interpret=_interpret())


def neighbor_sample_cached(indptr, cache, block_slots, targets, rand, *,
                           block_e: int, max_block: int):
    """Out-of-core-topology fanout sample: per-target edge blocks come
    from the (C, 1, block_e) HBM block cache through the ``block_slots``
    indirection table instead of the full device-resident edge array.
    Residency is the caller's contract (``DeviceEdgeBlockCache`` resolves
    the planned block set before dispatch); results are bit-identical to
    ``neighbor_sample``."""
    if not _ENABLED:
        return ref.neighbor_sample_cached(
            indptr, block_slots, targets.astype(jnp.int32),
            rand.astype(jnp.int32), from_rows(cache, block_e),
            block_e=block_e, max_block=max_block)
    return _sample_cached_pl(indptr.astype(jnp.int32),
                             block_slots.astype(jnp.int32),
                             targets.astype(jnp.int32),
                             rand.astype(jnp.int32), cache,
                             block_e=block_e, max_block=max_block,
                             interpret=_interpret())


def sample_khop_kernel(indptr, indices, targets, fanouts, *, key,
                       max_degree: int):
    """K-hop GraphSAGE sampling via the ``neighbor_sample`` kernel.

    Per hop: fold the hop index into ``key``, draw rand bits shaped like
    the frontier + fanout, flatten the frontier, and run the kernel.  The
    key/rand derivation matches ``ISPGraph.sample_khop`` bit-for-bit, so
    the pallas and isp backends sample identical node IDs for the same
    per-batch key.  Returns the per-hop ID tensors
    [(M,), (M, f1), (M, f1, f2), ...].
    """
    hops = [targets.astype(jnp.int32)]
    frontier = hops[0]
    for i, f in enumerate(fanouts):
        rand = jax.random.randint(jax.random.fold_in(key, i),
                                  frontier.shape + (f,), 0, 2**31 - 1)
        flat = frontier.reshape(-1)
        nxt = neighbor_sample(indptr, indices, flat,
                              rand.reshape(flat.shape[0], f),
                              max_degree=max_degree)
        frontier = nxt.reshape(frontier.shape + (f,))
        hops.append(frontier)
    return hops


def feature_gather_rows(table, ids, *, width: int):
    """(N, 1, Wp) row table, ids (...,) int32 -> (..., width) row gather:
    ONE pallas_call per hop tensor, staging TILE_ROWS rows per grid
    step."""
    flat = ids.reshape(-1).astype(jnp.int32)
    if not _ENABLED:
        out = ref.feature_gather_mean(from_rows(table, width), flat[:, None])
    else:
        out = _rows_pl(table, flat, width=width, interpret=_interpret())
    return out.reshape(ids.shape + (width,)).astype(table.dtype)


def feature_gather_cached(cache, slot_of, ids, *, width: int):
    """(C, 1, Wp) HBM row cache, (N+1,) int32 slot table, ids (...,) int32
    -> (..., width): the device-cache read path — indirection lookup +
    tiled row gather in one pallas_call.  Every id must be resident
    (slot != -1); ``storage.devcache.DeviceFeatureCache`` guarantees that
    by resolving misses before dispatch."""
    flat = ids.reshape(-1).astype(jnp.int32)
    if flat.shape[0] == 0:
        return jnp.zeros(ids.shape + (width,), cache.dtype)
    if not _ENABLED:
        out = ref.feature_gather_cached(from_rows(cache, width), slot_of,
                                        flat)
    else:
        out = _cached_pl(cache, slot_of, flat, width=width,
                         interpret=_interpret())
    return out.reshape(ids.shape + (width,)).astype(cache.dtype)


def decode_attention(q, k, v, valid_len, window=0, *, block_s: int = 512):
    """Flash-decode over a KV cache; pads S up to a block multiple."""
    if not _ENABLED:
        return ref.decode_attention(q, k, v, valid_len, window)
    S = k.shape[1]
    block_s = min(block_s, max(128, S))
    pad = (-S) % block_s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return _decode_pl(q, k, v, valid_len, window, block_s=block_s,
                      interpret=_interpret())


def ssd_chunk_scan(x, dt, A, B, C, *, chunk: int = 128):
    """Mamba-2 SSD scan; pads the sequence up to a chunk multiple."""
    if not _ENABLED:
        return ref.ssd_chunk_scan(x, dt, A, B, C, chunk=chunk)
    s = x.shape[1]
    chunk = min(chunk, s) if s % chunk == 0 else chunk
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, state = _ssd_pl(x, dt, A, B, C, chunk=chunk, interpret=_interpret())
    return y[:, :s], state


def flash_attention_bshd(q, k, v, *, block_q: int = 256, block_k: int = 256,
                         causal: bool = True):
    """Training flash attention on the model's (B, S, H, D) layout.

    Scores/probabilities never leave VMEM (fwd + custom-VJP bwd kernels);
    GQA handled by BlockSpec index_map.  Blocks are clipped to divisors
    of S.  Used by the LM when ``cfg.attn_impl == "flash"``.
    """
    S = q.shape[1]
    def fit(b):
        b = min(b, S)
        while S % b:
            b //= 2
        return max(b, 1)
    bq, bk = fit(block_q), fit(block_k)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = _flash_pl(qt, kt, vt, bq, bk, causal, _interpret())
    return jnp.swapaxes(out, 1, 2)
