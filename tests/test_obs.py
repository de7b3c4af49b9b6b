"""Unified telemetry layer: registry thread-safety, stable histogram
buckets, closed/ordered spans in the Perfetto export, associativity of
snapshot merging, canonical-name mapping, an end-to-end pipeline run
proving telemetry files are produced without perturbing bits, and the
same spans and step markers in a ``jax.profiler`` trace."""

import collections
import json
import os
import subprocess
import sys
import threading

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import obs
from repro.obs import names
from repro.obs.metrics import (HIST_BUCKETS, HIST_EDGES, MetricsRegistry,
                               bucket_index, idle_fraction, merge_snapshots)
from repro.obs.tracer import SpanTracer


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_concurrent_increments_sum_exactly():
    """Increments from >= 4 threads land exactly: per-thread shards mean
    no lost updates, and the snapshot merge adds them all back up."""
    reg = MetricsRegistry()
    threads, per_thread = 6, 10_000

    def worker(k):
        for _ in range(per_thread):
            reg.inc("store.requests")
            reg.inc("store.bytes_fetched", 4096)
            if k % 2 == 0:
                reg.observe("pipeline.stage_latency_s", 1e-3)

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = reg.snapshot()
    assert snap["store.requests"] == threads * per_thread
    assert snap["store.bytes_fetched"] == threads * per_thread * 4096
    hist = snap["pipeline.stage_latency_s"]
    assert hist["count"] == (threads // 2) * per_thread
    assert sum(hist["buckets"]) == hist["count"]


def test_histogram_bucket_edges_stable():
    """Fixed log2 edges: data-independent, index computable, monotone."""
    assert len(HIST_EDGES) == HIST_BUCKETS - 1
    assert all(b == a * 2 for a, b in zip(HIST_EDGES, HIST_EDGES[1:]))
    # same value -> same bucket regardless of registry/order/history
    for v in (0.0, 1e-9, 2 ** -20, 1e-3, 0.5, 1.0, 1.5, 2.0, 1e6, 1e30):
        i = bucket_index(v)
        assert i == bucket_index(v)
        assert 0 <= i < HIST_BUCKETS
        if 0 < i < HIST_BUCKETS - 1:
            assert HIST_EDGES[i - 1] <= v < HIST_EDGES[i]
    # boundary values land in the bucket they open
    assert bucket_index(HIST_EDGES[0]) == 1
    assert bucket_index(HIST_EDGES[10]) == 11
    # two registries observing the same stream agree bucket-for-bucket
    a, b = MetricsRegistry(), MetricsRegistry()
    vals = [1e-6, 3e-4, 0.02, 0.02, 7.0]
    for v in vals:
        a.observe("h", v)
    for v in reversed(vals):
        b.observe("h", v)
    assert a.snapshot()["h"]["buckets"] == b.snapshot()["h"]["buckets"]


@settings(max_examples=50)
@given(st.lists(st.integers(0, 100), min_size=9, max_size=9))
def test_merge_snapshots_associative(vals):
    """(a + b) + c == a + (b + c) for counter and histogram entries."""
    def mk(sub):
        # integer-valued floats: addition is exact, so the float sums
        # in the merged histograms are associative bit-for-bit
        h = {"buckets": [0] * HIST_BUCKETS, "count": 0, "sum": 0.0}
        for v in sub:
            h["buckets"][bucket_index(float(v))] += 1
            h["count"] += 1
            h["sum"] += float(v)
        return {"store.hits": sub[0], "store.misses": sub[1] * 2,
                "lat": h}

    a, b, c = mk(vals[0:3]), mk(vals[3:6]), mk(vals[6:9])
    left = merge_snapshots(merge_snapshots(a, b), c)
    right = merge_snapshots(a, merge_snapshots(b, c))
    assert left == right
    # commutative over numeric entries too
    assert merge_snapshots(a, b) == merge_snapshots(b, a)


def test_idle_fraction_shared_helper():
    """The single copy both stats dataclasses delegate to."""
    from repro.core.loader import RunStats
    from repro.core.pipeline import PipelineStats
    assert idle_fraction(0.0, 0.0) == 0.0
    assert idle_fraction(1.0, 3.0) == 0.25
    rs = RunStats(steps=4, idle_s=1.0, busy_s=3.0, wall_s=4.0)
    ps = PipelineStats(batches=4, consumer_idle_s=1.0, consumer_busy_s=3.0)
    assert rs.idle_fraction == ps.idle_fraction == 0.25


# ---------------------------------------------------------------------------
# span tracer + Perfetto export
# ---------------------------------------------------------------------------

def test_exported_spans_closed_and_ordered(tmp_path):
    """Every exported span is a complete event (closed by construction)
    and, per lane, timestamps are monotone with sibling spans
    non-overlapping (nested spans must be contained)."""
    tracer = SpanTracer()

    def lane(name, n):
        for i in range(n):
            with tracer.span("work", {"batch": i, "lane": name}):
                with tracer.span("inner", {"batch": i, "lane": name}):
                    pass

    ts = [threading.Thread(target=lane, args=(f"lane-{k}", 25))
          for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()

    path = tmp_path / "trace.json"
    tracer.export(str(path))
    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {e["ph"] for e in events} <= {"X", "M"}      # all closed
    assert len(spans) == 4 * 25 * 2
    lanes = {m["args"]["name"] for m in metas}
    assert lanes == {f"lane-{k}" for k in range(4)}
    by_tid = {}
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0
        by_tid.setdefault(e["tid"], []).append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        for prev, nxt in zip(evs, evs[1:]):
            assert nxt["ts"] >= prev["ts"]              # monotone per lane
            # non-overlapping: disjoint, or fully nested
            disjoint = nxt["ts"] >= prev["ts"] + prev["dur"]
            nested = nxt["ts"] + nxt["dur"] <= prev["ts"] + prev["dur"]
            assert disjoint or nested, (prev, nxt)


def test_trace_span_noop_when_uninstalled():
    assert obs.active_session() is None
    assert not obs.tracing()
    span = obs.trace_span("anything", batch=0)
    assert span is obs.NULL_SPAN                        # shared, no alloc
    with span:
        pass
    obs.tick()                                          # no-op, no error


def test_session_install_uninstall(tmp_path):
    s = obs.ObsSession(trace_path=str(tmp_path / "t.json"),
                       metrics_path=str(tmp_path / "m.jsonl"),
                       metrics_interval_s=60.0)
    obs.install(s)
    try:
        assert obs.tracing()
        with obs.trace_span("step", batch=7, lane="consumer"):
            obs.metric_inc("train.steps")
    finally:
        s.close()
    assert not obs.tracing()                            # uninstalled
    trace = json.loads((tmp_path / "t.json").read_text())
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["name"] == "step"
    assert xs[0]["args"]["batch"] == 7
    lines = (tmp_path / "m.jsonl").read_text().splitlines()
    assert lines, "final snapshot missing"
    snap = json.loads(lines[-1])["metrics"]
    assert snap["train.steps"] == 1
    s.close()                                           # idempotent


# ---------------------------------------------------------------------------
# canonical names (satellite: counter-naming drift)
# ---------------------------------------------------------------------------

def test_canonical_names_single_source():
    """The emitters' key tuples ARE the canonical table's leaves."""
    from repro.storage.store import IOContext
    assert IOContext.FAULT_KEYS == names.FAULT_KEYS
    assert IOContext.KEYS == names.STORE_IO_KEYS + names.FAULT_KEYS
    assert names.canonical("store", "hits") == "store.hits"
    assert names.canonical("store", "retries") == "store.faults.retries"
    assert names.canonical("devcache", "bytes_uploaded") == \
        "devcache.bytes_uploaded"


def test_flatten_stats_maps_tree_to_canonical():
    stats = {
        "store": {"requests": 10, "block_fetches": 4, "bytes_fetched": 8192,
                  "hits": 6, "misses": 4, "evictions": 1, "retries": 2,
                  "io_errors": 1, "short_reads": 0, "corrupt_blocks": 0,
                  "timeouts": 0, "kind": "disk"},
        "devcache": {"hits": 30, "misses": 10, "evictions": 5,
                     "preload_rows": 8, "bytes_uploaded": 4096,
                     "policy": "lru"},
        "oracle": {"window": 4, "windows_built": 2, "batches_replayed": 8,
                   "errors": 0, "timeouts": 0},
        "lane_stall_restarts": 1, "lane_failures": 0, "prefetched": 12,
        "degraded": False, "stage_s": {"sample": 0.5},
    }
    flat = names.flatten_stats(stats)
    assert flat["store.requests"] == 10
    assert flat["store.faults.retries"] == 2
    assert flat["store.hit_rate"] == 0.6
    assert flat["devcache.hit_rate"] == 0.75
    assert flat["oracle.batches_replayed"] == 8
    assert flat["pipeline.lane_stall_restarts"] == 1
    assert flat["pipeline.degraded"] == 0
    assert flat["pipeline.stage_s.sample"] == 0.5
    assert "kind" not in json.dumps(list(flat))         # non-metrics dropped


# ---------------------------------------------------------------------------
# end to end: telemetry files from a real pipeline, bits unperturbed
# ---------------------------------------------------------------------------

def _run_spec(spec, g, steps=4):
    import jax

    from repro.core import (GNNConfig, GraphSAGE, build_pipeline,
                            build_train_step, train_loop)
    from repro.optim import adamw
    losses = []
    pipe = build_pipeline(spec, g)
    try:
        gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim, hidden=16,
                                  n_classes=int(g.labels.max()) + 1,
                                  fanouts=spec.effective_fanouts))
        opt = adamw(3e-3)
        step = build_train_step(pipe, gnn, opt)
        state = {"params": gnn.init(jax.random.key(0)), "opt": None,
                 "step": 0}
        state["opt"] = opt.init(state["params"])
        state, _ = train_loop(
            pipe, step, state, steps=steps,
            on_step=lambda i, s, m: losses.append(float(m["loss"])))
    finally:
        pipe.close()
    return losses


def _disk_spec(store_dir, obs_spec=None):
    """A disk-backed overlapped pallas pipeline with a device row cache:
    every lane, the devcache stages and the store's preads run."""
    from repro.core.config import (BackendSpec, CacheTierSpec, ObsSpec,
                                   PipelineSpec, PrefetchSpec, StoreSpec)
    return PipelineSpec(
        backend=BackendSpec(name="pallas"),
        store=StoreSpec(kind="disk", path=str(store_dir), io_threads=2),
        cache_tiers=(
            CacheTierSpec(tier="host", policy="lru", capacity_mb=0.5,
                          arrays=()),
            CacheTierSpec.device(rows=48, policy="lru")),
        prefetch=PrefetchSpec(depth=2, overlap=True, stage_depth=2),
        batch_size=8, obs=obs_spec or ObsSpec())


def test_pipeline_telemetry_end_to_end(small_graph, tmp_path):
    """A disk-backed pallas+devcache run with telemetry on writes a
    Perfetto-loadable trace (pipeline/disk spans attributed to batches)
    and JSONL snapshots with the per-tier counters — and its loss
    trajectory is repr-identical to the telemetry-off twin."""
    from repro.core.config import ObsSpec
    trace_path = tmp_path / "trace.json"
    metrics_path = tmp_path / "metrics.jsonl"

    on = _run_spec(_disk_spec(tmp_path / "gs",
                              ObsSpec(trace_path=str(trace_path),
                                      metrics_path=str(metrics_path),
                                      metrics_interval_s=0.05)),
                   small_graph)
    off = _run_spec(_disk_spec(tmp_path / "gs"), small_graph)
    assert [repr(x) for x in on] == [repr(x) for x in off]

    trace = json.loads(trace_path.read_text())
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    # pipeline lanes + consumer + devcache + disk preads all present
    for stage in ("sample", "resolve", "admit",
                  "consume.step", "devcache.plan", "disk.pread"):
        assert by_name.get(stage), f"no {stage} spans in {sorted(by_name)}"
    lanes = {m["args"]["name"] for m in trace["traceEvents"]
             if m["ph"] == "M"}
    assert {"overlap-sample", "overlap-resolve", "overlap-admit",
            "consumer"} <= lanes, lanes
    # disk preads carry batch attribution inherited via IOContext
    assert any(e.get("args", {}).get("batch") is not None
               for e in by_name["disk.pread"])

    lines = metrics_path.read_text().splitlines()
    assert lines
    snap = json.loads(lines[-1])["metrics"]
    for k in ("store.hits", "store.misses", "store.bytes_fetched",
              "store.hit_rate", "devcache.hit_rate",
              "store.faults.retries"):
        assert k in snap, (k, sorted(snap))
    assert snap["store.bytes_fetched"] > 0


# ---------------------------------------------------------------------------
# the profiler sink: the same spans, and step markers, in a jax trace
# ---------------------------------------------------------------------------

PROFILED_STEPS = 4


def _tracereduce():
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import tracereduce
    return tracereduce


def _profile(trace_dir, fn):
    """``fn()`` under ``jax.profiler`` (Python calls untraced, as the
    benchmark's traced run has it)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _host_events(trace_dir):
    """(name, stats) of every host-plane event of the profile."""
    import glob

    import jax
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def profiled_run(small_graph, tmp_path_factory):
    """The disk-backed overlapped run under the profiler, with no
    ``ObsSession`` installed: (losses, profile directory)."""
    tmp = tmp_path_factory.mktemp("profiled")
    assert obs.active_session() is None
    losses = _profile(tmp / "trace", lambda: _run_spec(
        _disk_spec(tmp / "gs"), small_graph, steps=PROFILED_STEPS))
    return losses, tmp / "trace"


def test_profiler_trace_holds_the_program_spans(profiled_run):
    """With no session, every lane stage, store read group and consumer
    span and a step marker per step land on the profile's host plane,
    where the benchmark's reduction reads them: one stage span per
    batch, and read groups attributed to the batch they were issued
    for.  The per-block preads stay off the profile."""
    _, trace_dir = profiled_run
    tr = _tracereduce().load(str(trace_dir))
    count = collections.Counter(s.name for s in tr.spans)
    for name in ("sample", "resolve", "admit", "disk.read_group",
                 "consume.wait", "consume.step", "train"):
        assert count[name], f"no {name} spans in {sorted(count)}"
    assert not count["disk.pread"]
    for name in ("consume.wait", "consume.step", "train"):
        assert count[name] == PROFILED_STEPS, (name, count[name])
    events = _host_events(trace_dir)
    assert sorted(st["step_num"] for n, st in events if n == "train") == \
        list(range(PROFILED_STEPS))
    for stage in ("sample", "resolve", "admit"):
        batches = [st["batch"] for n, st in events if n == stage]
        assert len(batches) == len(set(batches)), (stage, batches)
        assert set(range(PROFILED_STEPS)) <= set(batches), (stage, batches)
    groups = [st for n, st in events if n == "disk.read_group"]
    assert all({"array", "ranges"} <= set(st) for st in groups)
    assert any("batch" in st for st in groups)


def test_profiled_run_is_bit_identical(profiled_run, small_graph,
                                       tmp_path):
    """Spans only observe the clock: the loss trajectory under the
    profiler is repr-identical to the same run without it."""
    on, _ = profiled_run
    off = _run_spec(_disk_spec(tmp_path / "gs"), small_graph,
                    steps=PROFILED_STEPS)
    assert [repr(x) for x in on] == [repr(x) for x in off]


def test_trace_span_fast_path_with_jax_and_no_profiler():
    """With jax imported but no profiler session and no ObsSession, the
    hooks hand back the shared null span and report tracing off."""
    import jax
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.active_session() is None
    assert obs.trace_span("disk.read_group", ranges=3, batch=None) is \
        obs.NULL_SPAN
    assert obs.session_span("disk.pread", block=3) is obs.NULL_SPAN
    assert obs.step_span("train", 0) is obs.NULL_SPAN
    assert not obs.tracing()


def test_obs_stays_importable_without_jax():
    """``obs`` resolves the profiler hook lazily: a process that never
    imports jax gets the null span and never loads jax through it."""
    code = ("import sys\n"
            "from repro import obs\n"
            "assert obs.trace_span('x', batch=1) is obs.NULL_SPAN\n"
            "assert obs.step_span('train', 0) is obs.NULL_SPAN\n"
            "assert not obs.tracing()\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_span_reaches_profiler_and_session_together(tmp_path):
    """With a profiler and a session both on, one ``trace_span`` is an
    event of the profile (its ``None`` attributes and ``lane`` left
    out) and a span of the session's Perfetto export (on its lane); a
    ``session_span`` is the export's alone."""
    s = obs.install(obs.ObsSession(trace_path=str(tmp_path / "t.json")))

    def body():
        assert obs.tracing()
        with obs.trace_span("consume.wait", batch=5, block=None,
                            lane="consumer"):
            with obs.session_span("disk.pread", block=9, lane="consumer"):
                pass
    try:
        _profile(tmp_path / "trace", body)
    finally:
        s.close()
    events = [(n, st) for n, st in _host_events(tmp_path / "trace")
              if n in ("consume.wait", "disk.pread")]
    assert events == [("consume.wait", {"batch": 5})]
    trace = json.loads((tmp_path / "t.json").read_text())
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted((e["name"], e["args"]) for e in xs) == \
        [("consume.wait", {"batch": 5}), ("disk.pread", {"block": 9})]
    lanes = [m["args"]["name"] for m in trace["traceEvents"]
             if m["ph"] == "M"]
    assert lanes == ["consumer"]
