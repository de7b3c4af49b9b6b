"""The benchmark's own yardstick, on the CPU: the graph generator, the
FLOP and byte counts, the peak table and the trace reduction."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import graphgen  # noqa: E402
import peaks  # noqa: E402
import tracereduce  # noqa: E402
from tracereduce import Op, Span, Trace  # noqa: E402

SMALL = {"name": "small", "num_nodes": 5000, "avg_degree": 12.5,
         "max_degree": 300, "feat_dim": 7, "n_classes": 5,
         "graph_seed": 11}


@pytest.fixture(scope="module")
def small():
    return graphgen.make_graph(SMALL)


def test_published_node_and_entry_counts(small):
    assert small["indptr"].shape == (5001,)
    assert small["indptr"][-1] == 62500 == small["indices"].shape[0]
    assert small["features"].shape == (5000, 7)
    assert small["features"].dtype == np.float32
    assert small["labels"].min() >= 0 and small["labels"].max() < 5


def test_degrees_mean_and_bounds(small):
    deg = np.diff(small["indptr"])
    assert deg.mean() == pytest.approx(12.5, abs=1e-12)
    assert deg.min() >= 1 and deg.max() <= 300
    # a power law with this mean puts some hubs near the cap
    assert deg.max() > 100


def test_configuration_model_lists(small):
    """Each node appears in other lists exactly as often as its degree."""
    deg = np.diff(small["indptr"])
    seen = np.bincount(small["indices"], minlength=5000)
    np.testing.assert_array_equal(seen, deg)


def test_generator_is_deterministic(small):
    again = graphgen.make_graph(SMALL)
    for k in small:
        np.testing.assert_array_equal(small[k], again[k])
    other = graphgen.make_graph(dict(SMALL, graph_seed=12))
    assert not np.array_equal(small["indices"], other["indices"])


@pytest.mark.parametrize("mean,dmax", [(50.0, 1024), (50.517278480573324,
                                                      16384), (3.0, 10)])
def test_solved_exponent_gives_the_mean(mean, dmax):
    a = graphgen.solve_alpha(mean, dmax)
    d = np.arange(1, dmax + 1, dtype=np.float64)
    p = d ** -a
    assert (p * d).sum() / p.sum() == pytest.approx(mean, rel=1e-9)


def test_graph_cache_round_trip(tmp_path):
    first = graphgen.load_or_make(SMALL, str(tmp_path))
    path = tmp_path / graphgen.graph_key(SMALL)
    assert sorted(os.listdir(path)) == ["features.npy", "indices.npy",
                                        "indptr.npy", "labels.npy"]
    again = graphgen.load_or_make(SMALL, str(tmp_path))
    for k in first:
        np.testing.assert_array_equal(first[k], again[k])
    assert graphgen.graph_key(dict(SMALL, avg_degree=10)) != \
        graphgen.graph_key(SMALL)


def test_step_flops_against_hand_worked_shapes():
    # reddit: rows 512, 12,800 (hop 2 is only aggregated); layer 0 on
    # 13,312 rows at 602 x 256 twice, layer 1 on 512 rows at 256 x 256
    # twice, classifier 512 x 256 x 41; forward + backward = 3x
    fwd = (4 * 13312 * 602 * 256 + 4 * 512 * 256 * 256
           + 2 * 512 * 256 * 41)
    assert flops.step_matmul_flops(512, (25, 10), 602, 256, 41) == 3 * fwd
    assert 3 * fwd == 25053364224
    # products: layer 0 on 169,984 rows at 100 wide, layer 1 on 16,384,
    # layer 2 on 1,024
    assert flops.step_matmul_flops(1024, (15, 10, 5), 100, 256, 47) == \
        65983217664


def test_kernel_bytes_against_hand_worked_shapes():
    assert flops.hop_rows(512, (25, 10)) == [512, 12800, 128000]
    # per target: id + two offsets; per pick: word, entry, output
    assert flops.sample_bytes(512, 25) == 512 * 12 + 512 * 25 * 12
    assert flops.gather_bytes(10, 602) == 10 * (2 * 602 * 4 + 4)
    assert flops.gather_bytes(10, 602, cached=True) == 10 * (2 * 602 * 4
                                                            + 8)


def test_peak_table_knows_v5e_and_refuses_the_rest():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def _recorded():
    """A small trace in the reduction's own terms: one chip, a 100 ns
    window with a train step, a data-plane op overlapping it, and two
    idle gaps under the host's get_batch and train_step spans."""
    ops = [Op(0, "%fusion.1 = s32[64]{0} fusion(s32[64]{0} %p)",
              "jit_prepare", 5, 20),
           Op(0, "%neighbor_sample.2 = s32[8,4]{1,0} custom-call(...)",
              "jit_prepare", 15, 15),                       # overlaps
           Op(0, "%fusion.3 = f32[8,16]{1,0} fusion(...)", "jit_step", 50,
              30),
           Op(0, "%fusion.4 = f32[8]{0} fusion(...)", "jit_step", 120,
              10)]                                          # outside
    spans = [Span("window", 0, 100), Span("get_batch", 0, 45),
             Span("train_step", 45, 100)]
    return Trace(ops=ops, spans=spans, chips=1)


def test_trace_reduction_on_a_recorded_trace():
    s = tracereduce.summary(_recorded(), step_module=lambda m: m ==
                            "jit_step")
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(55e-9)       # [5,30) + [50,80)
    assert s["step_device_s"] == pytest.approx(30e-9)
    assert s["prep_device_s"] == pytest.approx(35e-9)
    assert s["device_ops"][0] == ["jit_step/fusion", pytest.approx(30e-9)]
    assert ["jit_prepare/neighbor_sample", pytest.approx(15e-9)] in \
        s["device_ops"]
    gaps = dict((k, v) for k, v in s["idle_gaps"])
    assert s["idle_gaps"][0] == ["get_batch", pytest.approx(20e-9)]
    assert gaps["train_step"] == pytest.approx(20e-9)  # [80, 100)
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(45e-9)


def test_kernel_and_module_names():
    op = Op(0, "%feature_gather_rows.5 = f32[128000,602]{1,0} "
               "custom-call(s32[2000,1,64]{2,1,0} %bitcast.23)", "", 0, 1)
    assert op.kernel == "feature_gather_rows"
    assert Op(0, "%copy-done", "", 0, 1).kernel == "copy-done"
    assert tracereduce.module_name("jit_step(12)") == "jit_step"
    mods = [(0, 10, "jit_prepare"), (12, 30, "jit_step")]
    assert tracereduce.module_at(mods, 15) == "jit_step"
    assert tracereduce.module_at(mods, 11) == ""


def test_a_gap_is_named_by_what_the_host_did_in_most_of_it():
    spans = [Span("train_loop", 0, 100), Span("get_batch", 30, 95),
             Span("train_step", 95, 99)]
    names = ("get_batch", "train_step", "train_loop")
    assert tracereduce.gap_label(spans, 20, 100, names) == "get_batch"
    assert tracereduce.gap_label(spans, 20, 40, names) == "train_loop"
    assert tracereduce.gap_label(spans, 100, 120, names) == "other"


def test_merge_and_gaps():
    assert tracereduce.merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    ops = [Op(0, "a", "m", 2, 2), Op(1, "b", "m", 0, 9)]
    assert tracereduce.idle_gaps(ops, 0, 0, 10) == [(0, 2), (4, 10)]
    assert tracereduce.busy_ns(ops, 1) == 9


def test_trace_load_reads_host_spans_of_a_real_profile(tmp_path):
    """``load`` on a profile the CPU records: the host spans are there,
    and a CPU plane is no device."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracereduce.load(str(tmp_path))
    assert tr.ops == []
    lo, hi = tr.window()
    assert hi > lo
