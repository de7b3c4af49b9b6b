"""In-storage processing service: wire-protocol framing, transports, the
pipelined client window, crash/reconnect classification, O_DIRECT reads,
and end-to-end isp-vs-host bit-identity (the paper's acceptance bar: the
pushdown must change *where* sampling runs, never *what* it computes)."""

import os
import struct
import threading
import time
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import build_pipeline, sample_khop
from repro.core.config import (BackendSpec, CacheTierSpec, IspSpec,
                               PipelineSpec, SamplerSpec, StoreSpec)
from repro.isp import protocol, transport
from repro.isp.client import IspClient, RemoteGraphStore, RemoteStoreError
from repro.isp.protocol import Command
from repro.isp.server import IspServer
from repro.storage import DiskStore, save_graph
from repro.storage.store import StoreReadError


@pytest.fixture(scope="module")
def disk_dir(small_graph, tmp_path_factory):
    path = tmp_path_factory.mktemp("ispstore")
    save_graph(small_graph, str(path))
    return str(path)


def _recv_from(buf: bytes):
    """A ``recv_exact`` over an in-memory byte string (raises
    ``TransportClosed`` at EOF, like a socket would)."""
    view = memoryview(buf)
    pos = [0]

    def recv_exact(n: int):
        if pos[0] + n > len(buf):
            raise transport.TransportClosed("eof")
        out = view[pos[0]:pos[0] + n]
        pos[0] += n
        return out

    return recv_exact


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

_DTYPES = ("<i4", "<i8", "<f4", "<f8", "|u1", "<u2")


@given(st.lists(st.sampled_from(_DTYPES), min_size=0, max_size=4),
       st.integers(min_value=0, max_value=2**32 - 1),
       st.integers(min_value=0, max_value=10_000),
       st.sampled_from([False, True]))
@settings(max_examples=30, deadline=None)
def test_frame_roundtrip(dtypes, rid, shape_seed, payload_crc):
    """Any (dtype, shape) mix survives encode -> read_message exactly:
    values, dtypes, shapes, meta, request id, and the reported wire size."""
    rng = np.random.default_rng(shape_seed)
    arrays = []
    for dt in dtypes:
        shape = tuple(int(s) for s in
                      rng.integers(0, 5, size=int(rng.integers(0, 4))))
        arrays.append((rng.integers(0, 100, size=shape) * 3)
                      .astype(np.dtype(dt)))
    meta = {"fanouts": [3, 2], "seed": int(rid % 7), "nested": {"k": "v"}}
    frame = protocol.encode(Command.SAMPLE_KHOP, rid, meta, arrays,
                            payload_crc=payload_crc)
    msg, nbytes = protocol.read_message(_recv_from(frame))
    assert nbytes == len(frame)
    assert msg.command == Command.SAMPLE_KHOP
    assert msg.request_id == rid
    assert msg.meta == meta
    assert not msg.is_reply and not msg.is_error
    assert len(msg.arrays) == len(arrays)
    for got, want in zip(msg.arrays, arrays):
        assert got.dtype == want.dtype
        assert got.shape == want.shape
        np.testing.assert_array_equal(got, want)


def test_reply_and_error_flags_roundtrip():
    frame = protocol.encode(Command.STATS, 7, {"error": "boom"}, [],
                            flags=protocol.FLAG_REPLY | protocol.FLAG_ERROR)
    msg, _ = protocol.read_message(_recv_from(frame))
    assert msg.is_reply and msg.is_error


def test_truncated_stream_is_transport_closed():
    """A peer dying mid-frame is a transport condition, not a decode bug."""
    frame = protocol.encode(Command.HELLO, 1, {}, [np.arange(10)])
    for cut in (0, 10, protocol.HEADER_BYTES, len(frame) - 1):
        with pytest.raises(transport.TransportClosed):
            protocol.read_message(_recv_from(frame[:cut]))


def _pack_header(magic=protocol.MAGIC, version=protocol.VERSION, command=1,
                 flags=0, rid=0, meta_len=0, payload_len=0, crc=None):
    head = protocol._HEADER.pack(magic, version, command, flags, rid,
                                 meta_len, payload_len, 0)
    if crc is None:
        from repro.storage.integrity import crc32c
        crc = crc32c(head[:-4])
    return head[:-4] + struct.pack("<I", crc)


def test_garbage_header_rejected():
    with pytest.raises(protocol.ProtocolError, match="truncated header"):
        protocol._parse_header(b"short")
    with pytest.raises(protocol.ProtocolError, match="bad magic"):
        protocol.read_message(_recv_from(_pack_header(magic=0xDEADBEEF)))
    with pytest.raises(protocol.ProtocolError, match="version"):
        protocol.read_message(_recv_from(_pack_header(version=99)))
    with pytest.raises(protocol.ProtocolError, match="CRC32C mismatch"):
        protocol.read_message(_recv_from(_pack_header(crc=0)))
    with pytest.raises(protocol.ProtocolError, match="meta length"):
        protocol.read_message(_recv_from(
            _pack_header(meta_len=protocol.MAX_META_BYTES + 1)))
    with pytest.raises(protocol.ProtocolError, match="payload length"):
        protocol.read_message(_recv_from(
            _pack_header(payload_len=protocol.MAX_PAYLOAD_BYTES + 1)))


def test_flipped_bit_in_header_rejected():
    """Any single corrupted header byte must fail the CRC (or an earlier
    field check) — never decode into a trusted length."""
    frame = protocol.encode(Command.HELLO, 3, {"a": 1}, [np.arange(4)])
    for i in range(protocol.HEADER_BYTES):
        bad = bytearray(frame)
        bad[i] ^= 0x40
        with pytest.raises(protocol.ProtocolError):
            protocol.read_message(_recv_from(bytes(bad)))


def test_payload_crc_detects_corruption():
    arr = np.arange(1024, dtype=np.int64)
    frame = protocol.encode(Command.GATHER_FEATURES, 1, {}, [arr],
                            payload_crc=True)
    bad = bytearray(frame)
    bad[-5] ^= 0x01         # flip one payload bit
    with pytest.raises(protocol.ProtocolError, match="payload CRC"):
        protocol.read_message(_recv_from(bytes(bad)))
    msg, _ = protocol.read_message(_recv_from(frame))   # clean copy is fine
    np.testing.assert_array_equal(msg.arrays[0], arr)


def test_descriptor_payload_length_mismatch_rejected():
    """Descriptors claiming more (or fewer) bytes than the payload holds
    are rejected before any allocation is trusted."""
    frame = protocol.encode(Command.HELLO, 1, {},
                            [np.arange(8, dtype=np.int32)])     # 32 B payload
    # graft a shorter payload_len under the same 32-byte descriptor
    head = _pack_header(command=int(Command.HELLO), meta_len=len(frame) -
                        protocol.HEADER_BYTES - 32, payload_len=16)
    with pytest.raises(protocol.ProtocolError, match="payload too short"):
        protocol.read_message(_recv_from(head + frame[protocol.HEADER_BYTES:]))


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------

def _echo_once(listener, n_messages=1):
    """Accept one connection and echo ``n_messages`` frames back as
    replies."""

    def run():
        conn = listener.accept(timeout=10.0)
        try:
            for _ in range(n_messages):
                msg, _ = protocol.read_message(conn.recv_exact)
                conn.send_bytes(protocol.encode(
                    msg.command, msg.request_id, {"echo": msg.meta},
                    msg.arrays, flags=protocol.FLAG_REPLY))
        finally:
            conn.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


@pytest.mark.parametrize("kind", ["unix", "tcp", "shm"])
def test_transport_roundtrip(kind, tmp_path):
    if kind == "unix":
        address = os.path.join(str(tmp_path), "t.sock")
    elif kind == "tcp":
        address = "127.0.0.1:0"
    else:
        address = f"isp-test-{os.getpid():x}-{int(time.time() * 1e6):x}"
    listener = transport.make_listener(kind, address)
    address = getattr(listener, "address", address)
    n = 4       # several frames so the shm ring wraps its cursors
    t = _echo_once(listener, n_messages=n)
    conn = transport.connect(kind, address, timeout=10.0)
    try:
        for i in range(n):
            arr = np.arange(100_000 + i, dtype=np.int64)
            conn.send_bytes(protocol.encode(Command.GATHER_FEATURES, i,
                                            {"i": i}, [arr]))
            msg, _ = protocol.read_message(conn.recv_exact)
            assert msg.is_reply and msg.request_id == i
            assert msg.meta == {"echo": {"i": i}}
            np.testing.assert_array_equal(msg.arrays[0], arr)
    finally:
        conn.close()
        t.join(timeout=10.0)
        listener.close()


# ---------------------------------------------------------------------------
# client window + reconnect against an in-process server
# ---------------------------------------------------------------------------

class _Loopback:
    """A real ``IspServer`` over a unix socket in a daemon thread, with
    the same accept-again-after-drop loop as ``run_server``."""

    def __init__(self, store, tmp, **server_kw):
        self.address = os.path.join(str(tmp), "isp.sock")
        self.listener = transport.make_listener("unix", self.address)
        self.server = IspServer(store, **server_kw)
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while True:
            try:
                conn = self.listener.accept(timeout=10.0)
            except (TimeoutError, OSError):
                return
            if self.server.serve_connection(conn):
                return

    def close(self):
        self.thread.join(timeout=10.0)
        self.listener.close()


@pytest.fixture()
def loopback(small_graph, disk_dir, tmp_path):
    store = DiskStore(disk_dir, cache_mb=2.0)
    lb = _Loopback(store, tmp_path)
    yield lb
    lb.close()
    store.close()


def test_window_pipelines_and_matches_by_request_id(small_graph, loopback):
    """Fill the in-flight window, then wait out of submission order:
    every reply must carry its own request's rows (matched by id, not
    arrival order), and the semaphore must never deadlock."""
    client = IspClient("unix", loopback.address, window=4)
    try:
        # fill the whole window (a slot frees only at wait()), then wait
        # in reverse submission order
        batches = [np.arange(i * 7, i * 7 + 5, dtype=np.int64) % \
                   small_graph.num_nodes for i in range(4)]
        pending = [client.submit(Command.GATHER_FEATURES, None, [ids])
                   for ids in batches]
        for ids, p in reversed(list(zip(batches, pending))):
            msg = client.wait(p)
            np.testing.assert_array_equal(
                msg.arrays[0], small_graph.features[ids])
        # concurrent producers share the window without deadlock
        errs = []

        def producer(w):
            try:
                ids = np.arange(w, w + 9, dtype=np.int64) \
                    % small_graph.num_nodes
                msg = client.call(Command.GATHER_FEATURES, None, [ids])
                np.testing.assert_array_equal(
                    msg.arrays[0], small_graph.features[ids])
            except Exception as e:      # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=producer, args=(w,))
                   for w in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errs
        assert client.counters["requests"] >= 16
        assert client.counters["bytes_tx"] > 0
        assert client.counters["bytes_rx"] > 0
        client.call(Command.SHUTDOWN)
    finally:
        client.close()


def test_reconnect_and_replay_after_transient_drop(small_graph, loopback):
    """A severed connection heals: the next call reconnects and replays,
    with the drop and the reconnect both on the books."""
    client = IspClient("unix", loopback.address, window=2,
                       connect_timeout=5.0)
    store = RemoteGraphStore(client)
    try:
        ids = np.arange(16, dtype=np.int64)
        np.testing.assert_array_equal(store.gather_features(ids),
                                      small_graph.features[ids])
        client.drop_connection()
        time.sleep(0.1)     # let the reader notice the dead socket
        np.testing.assert_array_equal(store.gather_features(ids),
                                      small_graph.features[ids])
        assert client.counters["disconnects"] >= 1
        assert client.counters["reconnects"] >= 1
        trace, hop_feats, labels = store.sample_khop_pushdown(
            np.arange(8, dtype=np.int32), (3, 2), seed=0)
        ref = sample_khop(small_graph, np.arange(8, dtype=np.int32), (3, 2),
                          seed=0)
        for h, r in zip(trace.hops, ref.hops):
            np.testing.assert_array_equal(h, r)
    finally:
        store.close()


def test_dead_server_is_classified_not_a_hang(small_graph, loopback):
    """After SHUTDOWN the server is gone for good: the next call must
    raise ``RemoteStoreError`` — an ``isinstance`` of ``StoreReadError``,
    so the pipeline's fault classification applies — within bounded time."""
    client = IspClient("unix", loopback.address, window=2,
                       connect_timeout=1.0, call_timeout=10.0)
    store = RemoteGraphStore(client)
    client.call(Command.SHUTDOWN)
    t0 = time.monotonic()
    with pytest.raises(StoreReadError):
        for _ in range(3):      # first calls may still drain the socket
            store.gather_features(np.arange(4, dtype=np.int64))
            time.sleep(0.05)
    assert time.monotonic() - t0 < 30.0
    assert client.counters["disconnects"] >= 1
    client.close()


def test_server_side_error_is_classified(small_graph, loopback):
    """A storage-side failure travels back as a FLAG_ERROR reply with the
    exception class, not a dead connection."""
    client = IspClient("unix", loopback.address, window=2)
    try:
        with pytest.raises(RuntimeError):
            # out-of-range ids make the server-side gather raise
            client.call(Command.GATHER_FEATURES, None,
                        [np.array([10**9], dtype=np.int64)])
        # the connection survives the failed command
        msg = client.call(Command.STATS)
        assert msg.meta["server"]["requests"] >= 2
        client.call(Command.SHUTDOWN)
    finally:
        client.close()


# ---------------------------------------------------------------------------
# pushdown bit-identity + the spawned-subprocess path
# ---------------------------------------------------------------------------

def _isp_spec(batch_size=8, seed=0, **store_kw):
    return PipelineSpec(
        backend=BackendSpec(name="host", n_workers=1, queue_depth=2),
        sampler=SamplerSpec(family="khop", fanouts=(3, 2)),
        store=StoreSpec(kind="disk", mode="isp", **store_kw),
        cache_tiers=(CacheTierSpec(tier="host", policy="lru",
                                   capacity_mb=4.0, arrays=()),),
        batch_size=batch_size, seed=seed)


def test_pushdown_bit_identical_to_host_sampling(small_graph, loopback):
    """The fused SAMPLE_KHOP equals host-side sample+gather exactly, for
    several seeds: hops, subgraph, per-hop dense features, labels."""
    client = IspClient("unix", loopback.address, window=4)
    store = RemoteGraphStore(client)
    try:
        g = small_graph
        for seed in (0, 1, 17):
            targets = np.random.default_rng(seed).integers(
                0, g.num_nodes, 8).astype(np.int32)
            trace, hop_feats, labels = store.sample_khop_pushdown(
                targets, (3, 2), seed=seed)
            ref = sample_khop(g, targets, (3, 2), seed=seed)
            assert len(trace.hops) == len(ref.hops)
            for h, r in zip(trace.hops, ref.hops):
                np.testing.assert_array_equal(h, r)
            np.testing.assert_array_equal(trace.subgraph_nodes,
                                          ref.subgraph_nodes)
            np.testing.assert_array_equal(trace.touched_nodes,
                                          ref.touched_nodes)
            for h, f in zip(ref.hops, hop_feats):
                np.testing.assert_array_equal(f, g.features[h])
            np.testing.assert_array_equal(labels, g.labels[targets])
        assert trace.io.get("requests", 0) > 0      # server-side I/O bill
    finally:
        store.close()


def test_minibatch_stream_bit_identical_mem_disk_isp(small_graph, tmp_path):
    """The full loader stack: host@mem, host@disk and isp (spawned
    subprocess server) must produce byte-identical minibatches — the
    invariant that makes loss-trajectory bit-identity inevitable."""
    g = small_graph

    def batches(spec, n=3):
        pipe = build_pipeline(spec, g)
        try:
            return [pipe.loader.get_batch(i) for i in range(n)]
        finally:
            pipe.close()

    base = dict(
        backend=BackendSpec(name="host", n_workers=1, queue_depth=2),
        sampler=SamplerSpec(family="khop", fanouts=(3, 2)),
        batch_size=8, seed=0)
    mem = batches(PipelineSpec(store=StoreSpec(kind="mem"), **base))
    disk = batches(PipelineSpec(
        store=StoreSpec(kind="disk", path=str(tmp_path / "d")),
        cache_tiers=(CacheTierSpec(tier="host", policy="lru",
                                   capacity_mb=4.0, arrays=()),), **base))
    isp = batches(PipelineSpec(
        store=StoreSpec(kind="disk", mode="isp", path=str(tmp_path / "i")),
        cache_tiers=(CacheTierSpec(tier="host", policy="lru",
                                   capacity_mb=4.0, arrays=()),), **base))
    for a, b in ((mem, disk), (mem, isp)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x.targets, y.targets)
            np.testing.assert_array_equal(x.labels, y.labels)
            for hx, hy in zip(x.hop_ids, y.hop_ids):
                np.testing.assert_array_equal(hx, hy)
            for fx, fy in zip(x.hop_feats, y.hop_feats):
                np.testing.assert_array_equal(fx, fy)


def test_loss_trajectory_bit_identical_isp_vs_host(small_graph, host_mesh,
                                                   rules, tmp_path):
    """4 training steps through a real spawned storage-server process:
    repr-equal losses vs host@disk, nonzero wire counters, clean server
    exit (no leaked subprocess)."""
    import jax
    import jax.numpy as jnp

    from repro.core import GNNConfig, GraphSAGE, build_train_step, train_loop
    from repro.optim import adamw

    g = small_graph
    gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim, hidden=16,
                              n_classes=int(g.labels.max()) + 1,
                              fanouts=(3, 2)))
    opt = adamw(1e-3)

    def run(spec):
        pipe = build_pipeline(spec, g, mesh=host_mesh)
        try:
            step = build_train_step(pipe, gnn, opt, host_mesh, rules)
            p = gnn.init(jax.random.key(0))
            state = {"params": p, "opt": opt.init(p),
                     "step": jnp.zeros((), jnp.int32)}
            losses = []
            with host_mesh:
                train_loop(pipe, step, state, steps=4,
                           on_step=lambda i, s, m:
                           losses.append(repr(float(m["loss"]))))
            stats = pipe.stats()
            proc = getattr(pipe.store, "server_proc", None)
        finally:
            pipe.close()
        return losses, stats, proc

    base = dict(
        backend=BackendSpec(name="host", n_workers=1, queue_depth=2),
        sampler=SamplerSpec(family="khop", fanouts=(3, 2)),
        cache_tiers=(CacheTierSpec(tier="host", policy="lru",
                                   capacity_mb=4.0, arrays=()),),
        batch_size=8, seed=0)
    host_losses, _, _ = run(PipelineSpec(
        store=StoreSpec(kind="disk", path=str(tmp_path / "host")), **base))
    isp_losses, isp_stats, proc = run(PipelineSpec(
        store=StoreSpec(kind="disk", mode="isp",
                        path=str(tmp_path / "isp")), **base))
    assert isp_losses == host_losses
    st = isp_stats["store"]
    assert st["kind"] == "isp"
    assert st["isp"]["bytes_tx"] > 0 and st["isp"]["bytes_rx"] > 0
    assert st["isp"]["disconnects"] == 0
    assert proc is not None and proc.poll() == 0    # reaped, exit 0


def test_server_crash_mid_epoch_surfaces_classified(small_graph, tmp_path):
    """kill -9 on the storage process mid-epoch: the loader must raise a
    classified ``StoreReadError`` promptly — not hang — with the
    disconnect counted."""
    spec = _isp_spec(path=str(tmp_path / "crash"))
    pipe = build_pipeline(spec, small_graph)
    try:
        pipe.loader.get_batch(0)            # healthy batch first
        proc = pipe.store.server_proc
        proc.kill()
        proc.wait(timeout=10.0)
        t0 = time.monotonic()
        with pytest.raises(StoreReadError):
            for i in range(1, 8):
                pipe.loader.get_batch(i)
        assert time.monotonic() - t0 < 60.0
        assert pipe.store.isp_counters()["disconnects"] >= 1
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# O_DIRECT read mode
# ---------------------------------------------------------------------------

def test_direct_io_reads_bit_identical(small_graph, disk_dir):
    buffered = DiskStore(disk_dir, cache_mb=1.0)
    direct = DiskStore(disk_dir, cache_mb=1.0, direct_io=True)
    try:
        ids = np.arange(0, small_graph.num_nodes, 7, dtype=np.int64)
        np.testing.assert_array_equal(direct.gather_features(ids),
                                      buffered.gather_features(ids))
        np.testing.assert_array_equal(direct.gather_labels(ids),
                                      buffered.gather_labels(ids))
        tr_d = sample_khop(direct, np.arange(8, dtype=np.int32), (3, 2),
                           seed=3)
        tr_b = sample_khop(buffered, np.arange(8, dtype=np.int32), (3, 2),
                           seed=3)
        for h, r in zip(tr_d.hops, tr_b.hops):
            np.testing.assert_array_equal(h, r)
        if direct.direct_io:        # ext4 supports it; tmpfs would degrade
            assert direct.stats()["direct_io"] is True
    finally:
        buffered.close()
        direct.close()


def test_direct_io_fallback_warns_and_works(disk_dir, monkeypatch):
    """Platforms without O_DIRECT fall back to buffered preads with one
    warning — never an error, never silent."""
    monkeypatch.delattr(os, "O_DIRECT", raising=False)
    with pytest.warns(UserWarning, match="direct_io requested but "
                                         "unavailable"):
        store = DiskStore(disk_dir, cache_mb=1.0, direct_io=True)
    try:
        assert store.direct_io is False
        assert store.stats()["direct_io"] is False
        assert store.gather_features(np.array([0, 1], np.int64)).shape[0] == 2
    finally:
        store.close()


def test_direct_io_default_off(disk_dir):
    store = DiskStore(disk_dir, cache_mb=1.0)
    try:
        assert store.direct_io is False
    finally:
        store.close()


# ---------------------------------------------------------------------------
# spec surface
# ---------------------------------------------------------------------------

def test_storage_process_env_pins_cpu(monkeypatch):
    """The spawned storage process never claims the trainer's chip."""
    from repro.isp.server import server_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = server_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"      # parent untouched


def test_isp_spec_validation():
    with pytest.raises(ValueError, match="mode"):
        StoreSpec(kind="mem", mode="isp")
    with pytest.raises(ValueError, match="transport"):
        IspSpec(transport="carrier-pigeon")
    with pytest.raises(ValueError, match="window"):
        IspSpec(window=0)
    # canonical: local mode carries no isp block
    assert StoreSpec(kind="disk").isp is None
    # isp mode defaults one in
    assert StoreSpec(kind="disk", mode="isp").isp == IspSpec()


def test_isp_mode_rejects_optimal_and_isp_backend():
    tiers = (CacheTierSpec(tier="host", policy="optimal", capacity_mb=2.0,
                           arrays=(), oracle_window=4),)
    with pytest.raises(ValueError, match="[Bb]elady|optimal"):
        PipelineSpec(backend=BackendSpec(name="host"),
                     sampler=SamplerSpec(family="khop", fanouts=(3, 2)),
                     store=StoreSpec(kind="disk", mode="isp"),
                     cache_tiers=tiers, batch_size=8)
    with pytest.raises(ValueError, match="backend"):
        PipelineSpec(backend=BackendSpec(name="isp"),
                     sampler=SamplerSpec(family="khop", fanouts=(3, 2)),
                     store=StoreSpec(kind="disk", mode="isp"),
                     batch_size=8)


def test_isp_spec_json_roundtrip():
    spec = _isp_spec(isp={"transport": "unix", "window": 6,
                          "server_cache": False})
    d = spec.to_dict()
    assert d["store"]["mode"] == "isp"
    assert d["store"]["isp"]["window"] == 6
    back = PipelineSpec.from_dict(d)
    assert back == spec
    assert back.store.isp.server_cache is False
