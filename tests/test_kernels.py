"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import rmat_graph
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention as decode_pl
from repro.kernels.feature_gather import feature_gather_mean as gather_pl
from repro.kernels.feature_gather import feature_gather_rows as rows_pl
from repro.kernels.layout import to_rows
from repro.kernels.neighbor_sample import neighbor_sample as sample_pl
from repro.kernels.ssd_chunk_scan import ssd_chunk_scan as ssd_pl


# ---------------------------------------------------------------------------
# feature_gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,F,N", [(8, 4, 32, 64), (16, 10, 128, 256),
                                     (1, 1, 8, 8), (32, 25, 602, 300)])
def test_feature_gather_sweep(M, K, F, N, dtype):
    rng = np.random.default_rng(M * K)
    table = jnp.asarray(rng.standard_normal((N, F)), dtype)
    ids = jnp.asarray(rng.integers(0, N, (M, K)), jnp.int32)
    out = gather_pl(to_rows(table), ids, width=F)
    expect = ref.feature_gather_mean(table, ids)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Kernels compile on the TPU, are interpreted on the CPU, and no
    other backend silently falls back to either."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


# ---------------------------------------------------------------------------
# neighbor_sample
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,e,M,S", [(64, 512, 8, 4), (256, 2048, 32, 10),
                                     (1024, 8192, 16, 25)])
def test_neighbor_sample_sweep(n, e, M, S):
    g = rmat_graph(n, e, seed=n)
    rng = np.random.default_rng(0)
    indptr = jnp.asarray(g.indptr, jnp.int32)
    indices = jnp.asarray(g.indices)
    targets = jnp.asarray(rng.integers(0, n, M), jnp.int32)
    rand = jnp.asarray(rng.integers(0, 2**31 - 1, (M, S)), jnp.int32)
    block_e = max(128, int(-(-int(g.degrees().max()) // 128) * 128))
    out = sample_pl(indptr, indices, targets, rand, block_e=block_e)
    expect = ref.neighbor_sample(indptr, indices, targets, rand)
    assert (np.asarray(out) == np.asarray(expect)).all()


def test_neighbor_sample_ops_wrapper(small_graph):
    g = small_graph
    rng = np.random.default_rng(1)
    targets = jnp.asarray(rng.integers(0, g.num_nodes, 16), jnp.int32)
    rand = jnp.asarray(rng.integers(0, 2**31 - 1, (16, 5)), jnp.int32)
    out = ops.neighbor_sample(jnp.asarray(g.indptr, jnp.int32),
                              jnp.asarray(g.indices), targets, rand,
                              max_degree=int(g.degrees().max()))
    expect = ref.neighbor_sample(jnp.asarray(g.indptr, jnp.int32),
                                 jnp.asarray(g.indices), targets, rand)
    assert (np.asarray(out) == np.asarray(expect)).all()


# ---------------------------------------------------------------------------
# tiled-kernel properties: tile boundaries + block-spanning neighbor lists
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(1, 70), st.sampled_from([1, 4, 10]),
       st.sampled_from([1, 3, 8, 16]))
def test_neighbor_sample_tile_boundaries(M, S, tile_m):
    """Tiled kernel == oracle for any (M, tile_m), including M smaller
    than, equal to, and not a multiple of the tile."""
    g = rmat_graph(128, 1024, seed=7)
    rng = np.random.default_rng(M * 31 + S * 7 + tile_m)
    indptr = jnp.asarray(g.indptr, jnp.int32)
    indices = jnp.asarray(g.indices, jnp.int32)
    targets = jnp.asarray(rng.integers(0, g.num_nodes, M), jnp.int32)
    rand = jnp.asarray(rng.integers(0, 2**31 - 1, (M, S)), jnp.int32)
    block_e = max(128, int(-(-int(g.degrees().max()) // 128) * 128))
    out = sample_pl(indptr, indices, targets, rand, block_e=block_e,
                    tile_m=tile_m)
    expect = ref.neighbor_sample(indptr, indices, targets, rand)
    assert (np.asarray(out) == np.asarray(expect)).all()


def test_neighbor_sample_list_spanning_two_blocks():
    """A max-degree (== block_e) neighbor list that straddles an edge-block
    boundary must be served exactly by the staged two-block tile."""
    block_e = 128
    degs = [100, block_e, 56]          # node 1's list occupies [100, 228)
    n = len(degs)
    indptr_np = np.zeros(n + 1, np.int64)
    np.cumsum(degs, out=indptr_np[1:])
    rng = np.random.default_rng(5)
    indices_np = rng.integers(0, n, indptr_np[-1]).astype(np.int32)
    indptr = jnp.asarray(indptr_np, jnp.int32)
    indices = jnp.asarray(indices_np)
    targets = jnp.asarray(np.array([1, 1, 0, 2, 1], np.int32))
    rand = jnp.asarray(rng.integers(0, 2**31 - 1, (5, 9)), jnp.int32)
    out = sample_pl(indptr, indices, targets, rand, block_e=block_e,
                    tile_m=2)
    expect = ref.neighbor_sample(indptr, indices, targets, rand)
    assert (np.asarray(out) == np.asarray(expect)).all()
    # the spanning list really does cross: its entries live in two blocks
    assert indptr_np[1] // block_e != (indptr_np[2] - 1) // block_e


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 130), st.sampled_from([1, 2, 5]),
       st.sampled_from([1, 3, 8, 64]))
def test_feature_gather_tile_boundaries(M, K, tile_m):
    """Tiled gather == oracle for any (rows, tile) combination."""
    rng = np.random.default_rng(M * 13 + K * 5 + tile_m)
    table = jnp.asarray(rng.standard_normal((96, 33)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 96, (M, K)), jnp.int32)
    out = gather_pl(to_rows(table), ids, width=33, tile_m=tile_m)
    expect = ref.feature_gather_mean(table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    flat = jnp.asarray(rng.integers(0, 96, M), jnp.int32)
    rows = rows_pl(to_rows(table), flat, width=33, tile_m=tile_m)
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray(table)[np.asarray(flat)])


def test_neighbor_sample_degree0_at_block_aligned_end():
    """A zero-degree node whose CSR offset sits at the end of an exactly
    block-aligned edge array must not fetch past the padded array (the
    in-kernel base clamp)."""
    block_e = 128
    degs = [128, 128, 0]               # E = 256, a multiple of block_e
    n = len(degs)
    indptr_np = np.zeros(n + 1, np.int64)
    np.cumsum(degs, out=indptr_np[1:])
    rng = np.random.default_rng(11)
    indices_np = rng.integers(0, n, indptr_np[-1]).astype(np.int32)
    indptr = jnp.asarray(indptr_np, jnp.int32)
    indices = jnp.asarray(indices_np)
    targets = jnp.asarray(np.array([2, 1, 2, 0], np.int32))
    rand = jnp.asarray(rng.integers(0, 2**31 - 1, (4, 6)), jnp.int32)
    out = sample_pl(indptr, indices, targets, rand, block_e=block_e,
                    tile_m=4)
    expect = ref.neighbor_sample(indptr, indices, targets, rand)
    assert (np.asarray(out) == np.asarray(expect)).all()
    # the degree-0 node really did sample itself
    assert (np.asarray(out)[0] == 2).all()


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 130), st.sampled_from([1, 3, 8, 64]))
def test_feature_gather_cached_tile_boundaries(R, tile_m):
    """Cached gather (indirection + tiled row gather) == oracle for any
    (rows, tile) combination, including R not a multiple of the tile."""
    from repro.kernels.feature_gather import feature_gather_cached as cached_pl
    rng = np.random.default_rng(R * 17 + tile_m)
    N, C, F = 96, 40, 33
    cache = jnp.asarray(rng.standard_normal((C, F)), jnp.float32)
    # a partial residency map: nodes 0..C-1 occupy a random slot permutation
    slot_of = np.full(N + 1, -1, np.int32)
    slot_of[:C] = rng.permutation(C)
    ids = jnp.asarray(rng.integers(0, C, R), jnp.int32)   # resident ids only
    out = cached_pl(to_rows(cache), jnp.asarray(slot_of), ids, width=F,
                    tile_m=tile_m)
    expect = np.asarray(cache)[slot_of[np.asarray(ids)]]
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_feature_gather_cached_ops_wrapper_nd():
    """ops.feature_gather_cached handles n-d hop tensors and matches the
    jnp oracle (which is also the ``use_kernels(False)`` fallback)."""
    rng = np.random.default_rng(6)
    N, C, F = 64, 16, 17
    cache = jnp.asarray(rng.standard_normal((C, F)), jnp.float32)
    slot_of = np.full(N + 1, -1, np.int32)
    resident = rng.choice(N, C, replace=False)
    slot_of[resident] = np.arange(C)
    ids = jnp.asarray(rng.choice(resident, (5, 3, 2)), jnp.int32)
    out = ops.feature_gather_cached(to_rows(cache), jnp.asarray(slot_of),
                                    ids, width=F)
    assert out.shape == (5, 3, 2, F)
    expect = ref.feature_gather_cached(cache, jnp.asarray(slot_of),
                                       np.asarray(ids).reshape(-1))
    np.testing.assert_array_equal(np.asarray(out).reshape(-1, F),
                                  np.asarray(expect))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(cache)[slot_of[np.asarray(ids)]])


def test_feature_gather_rows_single_call_nd():
    """ops.feature_gather_rows handles n-d hop tensors in one call."""
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((50, 17)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 50, (7, 3, 2)), jnp.int32)
    out = ops.feature_gather_rows(to_rows(table), ids, width=17)
    assert out.shape == (7, 3, 2, 17)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(ids)])


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,valid,window",
                         [(1, 128, 4, 4, 32, 128, 0),
                          (2, 256, 8, 2, 64, 200, 0),
                          (2, 256, 8, 2, 64, 200, 64),
                          (1, 512, 16, 1, 128, 1, 0),
                          (4, 128, 2, 2, 16, 77, 16)])
def test_decode_attention_sweep(B, S, Hq, Hkv, D, valid, window, dtype):
    rng = np.random.default_rng(S + Hq)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    out = decode_pl(q, k, v, valid, window, block_s=128)
    expect = ref.decode_attention(q, k, v, valid, window)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_pad_path():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 300, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 300, 2, 32)), jnp.float32)
    out = ops.decode_attention(q, k, v, 300, 0)
    expect = ref.decode_attention(q, k, v, 300, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd_chunk_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,g,n,chunk",
                         [(1, 32, 2, 4, 1, 8, 8),
                          (2, 64, 4, 8, 2, 16, 16),
                          (1, 128, 8, 16, 8, 32, 32),
                          (2, 48, 2, 8, 1, 4, 16)])
def test_ssd_chunk_scan_sweep(b, s, h, p, g, n, chunk):
    rng = np.random.default_rng(s * h)
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, h))) * 0.1,
                     jnp.float32)
    A = -jnp.asarray(np.abs(rng.standard_normal(h)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, g, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, g, n)), jnp.float32)
    y, st = ssd_pl(x, dt, A, B, C, chunk=chunk)
    ye, ste = ref.ssd_chunk_scan(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ste),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_matches_sequential_recurrence():
    """The chunked form must equal the naive per-step SSM recurrence."""
    rng = np.random.default_rng(3)
    b, s, h, p, n = 1, 24, 2, 4, 8
    x = rng.standard_normal((b, s, h, p)).astype(np.float32)
    dt = (np.abs(rng.standard_normal((b, s, h))) * 0.2).astype(np.float32)
    A = -np.abs(rng.standard_normal(h)).astype(np.float32)
    B = rng.standard_normal((b, s, 1, n)).astype(np.float32)
    C = rng.standard_normal((b, s, 1, n)).astype(np.float32)
    y, state = ref.ssd_chunk_scan(jnp.asarray(x), jnp.asarray(dt),
                                  jnp.asarray(A), jnp.asarray(B),
                                  jnp.asarray(C), chunk=8)
    # naive recurrence
    st = np.zeros((b, h, p, n), np.float32)
    ys = np.zeros((b, s, h, p), np.float32)
    for t in range(s):
        decay = np.exp(dt[:, t] * A[None, :])                     # (b,h)
        upd = np.einsum("bh,bn,bhp->bhpn", dt[:, t], B[:, t, 0], x[:, t])
        st = st * decay[:, :, None, None] + upd
        ys[:, t] = np.einsum("bn,bhpn->bhp", C[:, t, 0], st)
    np.testing.assert_allclose(np.asarray(y), ys, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(state), st, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# flash_attention (training kernel, fwd + custom-VJP bwd)
# ---------------------------------------------------------------------------

from repro.kernels.flash_attention import flash_attention  # noqa: E402


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk",
                         [(1, 64, 2, 2, 16, 16, 16),
                          (2, 128, 4, 2, 32, 32, 32),
                          (1, 256, 8, 1, 64, 64, 128)])
def test_flash_attention_fwd_sweep(B, S, Hq, Hkv, D, bq, bk, dtype):
    from repro.models.attention import mha_chunked
    rng = np.random.default_rng(S + Hq)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    pos = jnp.arange(S)
    qt, kt, vt = [jnp.moveaxis(x, 1, 2) for x in (q, k, v)]
    out = jnp.moveaxis(flash_attention(qt, kt, vt, bq, bk, True), 1, 2)
    ref_out = mha_chunked(q, k, v, q_positions=pos, k_positions=pos,
                          chunk_q=64, chunk_k=64)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_grads_match_autodiff():
    from repro.models.attention import mha_chunked
    rng = np.random.default_rng(11)
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    pos = jnp.arange(S)

    def loss_fa(q, k, v):
        qt, kt, vt = [jnp.moveaxis(x, 1, 2) for x in (q, k, v)]
        return jnp.sum(jnp.sin(flash_attention(qt, kt, vt, 32, 32, True)))

    def loss_ref(q, k, v):
        o = mha_chunked(q, k, v, q_positions=pos, k_positions=pos,
                        chunk_q=64, chunk_k=64)
        return jnp.sum(jnp.sin(jnp.moveaxis(o, 1, 2)))

    g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
