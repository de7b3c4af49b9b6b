"""The per-layer metrics that read the program's own spans, on a
hand-built trace: a lane's mean stage span, the store's read time per
step clipped to the window, and the device's idle time inside the
consumer's waits; each reader finds nothing, and says so, in a trace
without its spans."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from tracereduce import Op, Span, Trace  # noqa: E402

MS = 1e6                    # trace times are in ns
METRICS = os.path.join(BENCH, "metrics")


def _ctx(spans, ops=()):
    """Two steps in a 100 ms window, one chip."""
    tr = Trace(ops=list(ops), spans=[Span("window", 0, 100 * MS)]
               + list(spans), chips=1)
    return harness.Context(trace=tr, ops=tr.ops_in(*tr.window()), steps=2,
                           chips=1)


# Device busy [20, 40) and [60, 70) ms: idle [0, 20), [40, 60), [70, 100).
OPS = [Op(0, "%fusion.1 = f32[8]{0} fusion(...)", "jit_step", 20 * MS,
          20 * MS),
       Op(0, "%neighbor_sample.2 = s32[8]{0} custom-call(...)",
          "jit_prepare", 60 * MS, 10 * MS)]
SPANS = [
    # a lane's stage: the mean span that starts in the window, whole
    # (30 and 40 ms); one open before the window starts is left out
    Span("sample", -10 * MS, 20 * MS), Span("sample", 30 * MS, 60 * MS),
    Span("sample", 90 * MS, 130 * MS),
    Span("resolve", 10 * MS, 90 * MS),
    Span("admit", 40 * MS, 50 * MS), Span("admit", 120 * MS, 150 * MS),
    # read groups on two I/O threads overlap: their times add
    Span("disk.read_group", 10 * MS, 15 * MS),
    Span("disk.read_group", 12 * MS, 20 * MS),
    Span("disk.read_group", 30 * MS, 32 * MS),
    # waits [0, 30) and [50, 80): idle inside them 20 + 10 + 10 ms
    Span("consume.wait", 0, 30 * MS), Span("consume.step", 30 * MS,
                                           50 * MS),
    Span("consume.wait", 50 * MS, 80 * MS),
    Span("consume.step", 80 * MS, 100 * MS),
    # the benchmark's own labels are no program span
    Span("get_batch", 0, 30 * MS), Span("train_loop", 0, 100 * MS),
]

EXPECTED = {"sample_host_ms": 35.0, "resolve_host_ms": 80.0,
            "admit_host_ms": 10.0, "store_pread_ms": 7.5, "starved_ms": 20.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_built_trace(name):
    value = harness.reader(METRICS, name)(_ctx(SPANS, OPS))
    assert value == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_its_spans(name):
    """The parent of the program's spans, or an untraced run: no
    number, and no error."""
    read = harness.reader(METRICS, name)
    assert read(_ctx([Span("get_batch", 0, 30 * MS),
                      Span("train_step", 30 * MS, 50 * MS)], OPS)) is None
    assert read(harness.Context(trace=None, ops=[], steps=2,
                                chips=1)) is None


def test_a_traced_tiny_run_reports_the_program_spans(tmp_path):
    """The whole chain on the CPU: a ``--trace 1`` run of a tiny
    out-of-core cell puts the program's spans in the profile, and each
    span metric that lists the cell reports a number there."""
    import json
    import shutil

    import jax
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_compilation_cache_max_size",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    repo = os.path.dirname(BENCH)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    cfg = dict(harness.load_json(os.path.join(
        BENCH, "configs", "sage-reddit.json")), name="sage-tiny",
        num_nodes=3000, avg_degree=8, max_degree=100, feat_dim=24,
        n_classes=5, fanouts=[4, 3], hidden=16, batch_size=16, graph_seed=5)
    (tmp_path / "bench" / "configs" / "sage-tiny.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "limits" / "tiny.json").write_text(json.dumps(
        {"ids_mismatched": 0, "features_max_abs_diff": 0.0,
         "labels_mismatched": 0, "loss_gap": 1e-3, "grad_norm_gap": 0.08,
         "change_norm_gap": 0.05}))
    man = harness.load_json(os.path.join(repo, "BENCHMARK.json"))
    man["configs"].append({"name": "sage-tiny", "source": "test",
                           "file": "bench/configs/sage-tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny", "config": "sage-tiny",
                             "traffic": "pallas_ooc", "chips": 1,
                             "why": "test"})
    for m in man["per_layer"]:
        if m["name"] in EXPECTED:
            m["workloads"].append("tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    try:
        out = harness.run_cell(str(tmp_path), "tiny", 2**31 + 977, 0.1,
                               True, require_chip=False,
                               cache_dir=str(tmp_path / "cache"),
                               log=lambda s: None)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert out["correct"], out["checks"]
    for name in EXPECTED:
        assert out["metrics"][name]["value"] > 0, (name, out["metrics"])
