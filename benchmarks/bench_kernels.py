"""Kernel micro-benchmarks: wall time of the interpret-mode Pallas kernel
vs its jnp oracle (correctness delta + CPU-side timing; real-TPU timing is
out of scope for this container — see EXPERIMENTS.md §Roofline)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rmat_graph
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as decode_pl
from repro.kernels.feature_gather import feature_gather_mean as gather_pl
from repro.kernels.layout import to_rows
from repro.kernels.ssd_chunk_scan import ssd_chunk_scan as ssd_pl


def _time(fn, *args, reps=3):
    fn(*args)                                 # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    rng = np.random.default_rng(0)

    table = jnp.asarray(rng.standard_normal((2048, 256)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 2048, (64, 10)), jnp.int32)
    rows_table = to_rows(table)
    t_k = _time(lambda: gather_pl(rows_table, ids, width=256))
    t_r = _time(lambda: ref.feature_gather_mean(table, ids))
    err = float(jnp.abs(gather_pl(rows_table, ids, width=256)
                        - ref.feature_gather_mean(table, ids)).max())
    rows.append({"dataset": "feature_gather(64x10,256)",
                 "kernel_us": t_k, "oracle_us": t_r, "max_abs_err": err})

    q = jnp.asarray(rng.standard_normal((2, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 1024, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 1024, 2, 64)), jnp.float32)
    t_k = _time(lambda: decode_pl(q, k, v, 1024, 0, block_s=256))
    t_r = _time(lambda: ref.decode_attention(q, k, v, 1024, 0))
    err = float(jnp.abs(decode_pl(q, k, v, 1024, 0, block_s=256)
                        - ref.decode_attention(q, k, v, 1024, 0)).max())
    rows.append({"dataset": "decode_attn(B2,S1024,H8/2,D64)",
                 "kernel_us": t_k, "oracle_us": t_r, "max_abs_err": err})

    x = jnp.asarray(rng.standard_normal((1, 256, 4, 16)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((1, 256, 4))) * 0.1,
                     jnp.float32)
    A = -jnp.asarray(np.abs(rng.standard_normal(4)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    t_k = _time(lambda: ssd_pl(x, dt, A, B, C, chunk=64)[0])
    t_r = _time(lambda: ref.ssd_chunk_scan(x, dt, A, B, C, chunk=64)[0])
    err = float(jnp.abs(ssd_pl(x, dt, A, B, C, chunk=64)[0]
                        - ref.ssd_chunk_scan(x, dt, A, B, C, chunk=64)[0]
                        ).max())
    rows.append({"dataset": "ssd_scan(S256,H4,P16,N32)",
                 "kernel_us": t_k, "oracle_us": t_r, "max_abs_err": err})
    return rows
