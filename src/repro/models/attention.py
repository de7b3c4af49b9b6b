"""Attention: chunked (flash-style) training/prefill attention and
near-data sharded decode attention.

Training/prefill (``mha_chunked``): double-chunked online-softmax attention
— an lax.scan over KV chunks (and over Q chunks when the query side is
long) so no O(Sq*Sk) buffer ever materializes.  This is the pure-jnp
reference path used for dry-run lowering; on TPU the inner block is
replaced by the Pallas kernel (kernels/decode_attention.py shares the same
block math).

Decode (``sharded_decode_attention``): the KV cache is sharded over the
'model' mesh axis on the *sequence* dim.  Each shard reduces over its own
KV slice (partial max/sum/weighted-V) and only those O(B*H*D) partials are
combined across the mesh — the SmartSAGE near-data reduction applied to
attention (ship the subgraph, not the edge list).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.collectives import online_softmax_combine

NEG_INF = -1e30


def _chunk_scores_mask(q_pos, k_pos, window, causal: bool):
    """(cq, ck) boolean mask. window: traced scalar; <=0 means unlimited."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(diff.shape, jnp.bool_)
    if causal:
        ok = ok & (diff >= 0)
    ok = ok & jnp.where(window > 0, diff < window, True)
    return ok


def mha_chunked(q, k, v, *, q_positions, k_positions, window=0,
                causal: bool = True, chunk_q: int = 2048, chunk_k: int = 1024,
                scale: float | None = None, remat_chunks: bool = False,
                scores_bf16: bool = False):
    """Chunked multi-head attention with GQA.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D).
    q_positions: (Sq,) int32; k_positions: (Sk,) int32.
    window: int or traced scalar; sliding-window size (<=0 = full).
    Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    window = jnp.asarray(window, jnp.int32)

    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    nq, nk = Sq // cq, Sk // ck
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, cq, Sk, ck)

    qc = q.reshape(B, nq, cq, Hkv, group, D)
    kc = k.reshape(B, nk, ck, Hkv, D)
    vc = v.reshape(B, nk, ck, Hkv, D)
    qp = q_positions.reshape(nq, cq)
    kp = k_positions.reshape(nk, ck)

    def q_block(qi, q_blk, qpos_blk):
        # q_blk: (B, cq, Hkv, g, D)
        m0 = jnp.full((B, Hkv, group, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, group, cq), jnp.float32)
        o0 = jnp.zeros((B, Hkv, group, cq, D), jnp.float32)

        def kv_step(carry, inputs):
            m, l, o = carry
            k_blk, v_blk, kpos_blk = inputs
            if scores_bf16:
                # bf16 score pipeline: the (cq, ck) block and the exp'd
                # probabilities are materialized at 2 B/elem (the fp32
                # running max/sum/output stats keep the softmax stable) —
                # halves the dominant HBM-traffic term (§Perf).
                s = jnp.einsum("bqhgd,bkhd->bhgqk",
                               q_blk.astype(jnp.bfloat16),
                               k_blk.astype(jnp.bfloat16)) * scale
            else:
                s = jnp.einsum("bqhgd,bkhd->bhgqk",
                               q_blk.astype(jnp.float32),
                               k_blk.astype(jnp.float32)) * scale
            mask = _chunk_scores_mask(qpos_blk, kpos_blk, window, causal)
            s = jnp.where(mask[None, None, None], s,
                          jnp.asarray(NEG_INF, s.dtype))
            m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            p = jnp.exp(s.astype(jnp.float32) - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = p.astype(jnp.bfloat16) if scores_bf16 else p
            o_new = o * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", pv,
                v_blk.astype(pv.dtype)).astype(jnp.float32)
            return (m_new, l_new, o_new), None

        step = jax.checkpoint(kv_step) if remat_chunks else kv_step
        (m, l, o), _ = lax.scan(
            step, (m0, l0, o0),
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), kp))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        # (B, Hkv, g, cq, D) -> (B, cq, Hkv, g, D)
        return jnp.moveaxis(o, 3, 1)

    if nq == 1:
        out = q_block(0, qc[:, 0], qp[0])[:, :, :, :, :]
        out = out.reshape(B, Sq, Hq, D)
    else:
        outs = lax.map(lambda args: q_block(None, *args),
                       (jnp.moveaxis(qc, 1, 0), qp))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------

def decode_attention_local(q, cache_k, cache_v, valid_len, *, window=0,
                           scale: float | None = None):
    """Single-token attention over a (local) KV cache.

    q: (B, Hq, D); cache_k/v: (B, S, Hkv, D); valid_len: scalar int.
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = cache_k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    window = jnp.asarray(window, jnp.int32)

    qg = q.reshape(B, Hkv, group, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, cache_k.astype(jnp.float32)) * scale
    kpos = jnp.arange(S)
    ok = kpos < valid_len
    ok = ok & jnp.where(window > 0, kpos >= valid_len - window, True)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, cache_v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(B, Hq, D).astype(q.dtype)


def _decode_partials(q, k_slice, v_slice, kpos, valid_len, window, scale):
    """Per-shard online-softmax partials over a KV slice."""
    B, Hq, D = q.shape
    Hkv = k_slice.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_slice.astype(jnp.float32)) * scale
    ok = kpos < valid_len
    ok = ok & jnp.where(window > 0, kpos >= valid_len - window, True)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_slice.astype(jnp.float32))
    return m, l, o


def sharded_decode_attention(mesh, *, batch_axes, seq_axis: str = "model"):
    """Build the near-data decode attention (+ in-place cache update).

    Cache layout: (B, S, Hkv, D) with S sharded over ``seq_axis``.  The new
    token's K/V is written into whichever shard owns ``position``; attention
    partials are psum-combined.  Only O(B*Hq*D) bytes cross the mesh.

    Returns fn(q, new_k, new_v, cache_k, cache_v, position, window)
      -> (out, cache_k, cache_v).
    """

    def fn(q, new_k, new_v, cache_k, cache_v, position, window):
        B, S, Hkv, D = cache_k.shape
        Hq = q.shape[1]
        scale = 1.0 / math.sqrt(D)
        n_shards = mesh.shape[seq_axis]
        shard_len = S // n_shards

        def local(q, new_k, new_v, ck, cv, position, window):
            Bl, Hql = q.shape[0], q.shape[1]  # local (per-shard) sizes
            idx = lax.axis_index(seq_axis)
            start = idx * shard_len
            local_pos = position - start
            in_range = (local_pos >= 0) & (local_pos < shard_len)
            upd = jnp.clip(local_pos, 0, shard_len - 1)
            ck_u = lax.dynamic_update_slice(ck, new_k, (0, upd, 0, 0))
            cv_u = lax.dynamic_update_slice(cv, new_v, (0, upd, 0, 0))
            ck = jnp.where(in_range, ck_u, ck)
            cv = jnp.where(in_range, cv_u, cv)
            kpos = start + jnp.arange(shard_len)
            m, l, o = _decode_partials(q, ck, cv, kpos, position + 1,
                                       window, scale)
            out = online_softmax_combine(m, l, o, seq_axis)
            return out.reshape(Bl, Hql, D).astype(q.dtype), ck, cv

        cache_spec = P(batch_axes, seq_axis, None, None)
        qspec = P(batch_axes, None, None)
        newkv_spec = P(batch_axes, None, None, None)
        return shard_map(
            local, mesh=mesh,
            in_specs=(qspec, newkv_spec, newkv_spec, cache_spec, cache_spec,
                      P(), P()),
            out_specs=(qspec, cache_spec, cache_spec),
            check_vma=False,
        )(q, new_k, new_v, cache_k, cache_v, position, window)

    return fn
