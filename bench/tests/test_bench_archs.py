"""The model behind a configuration's ``arch``: ``bench/archs/graphsage.py``
gives the weights, FLOP count and check numbers that the harness computed
before it read ``arch`` (values pinned from those direct calls, on tiny
graphs with the configurations' own widths); an unknown ``arch`` is
refused by name; the batch is the configuration's per chip; a four-chip
cell runs whole on four virtual devices, and reads not correct with the
chips' exchange, half its batch or its state's update left out."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

SEED = 2**31 + 977
TINY = {"num_nodes": 2000, "batch_size": 8, "graph_seed": 5}
# Per leaf: sum of the weights and their last element.
PINNED = {
    "sage-reddit": {
        "weights": {
            "cls": (1.3910535108018394, -0.1031070351600647),
            "l0_neigh": (-35.69047018637618, -0.0895562469959259),
            "l0_self": (2.8929038687180118, -0.04603838548064232),
            "l1_neigh": (7.8300955106947185, 0.07161403447389603),
            "l1_self": (11.956151329875468, -0.058093633502721786)},
        "batch": 512, "flops": 25053364224,
        "losses": (3.7199671268463135, 3.7482409477233887,
                   3.733030080795288),
        "grad_norms": {"cls": 0.3816255404561061,
                       "cls_bias": 0.40524858405980235,
                       "l0_bias": 0.02490696351249056,
                       "l0_neigh": 0.2560811445615826,
                       "l0_self": 0.48243811074048193,
                       "l1_bias": 0.3333911204750023,
                       "l1_neigh": 0.2512200776091542,
                       "l1_self": 0.30198252128203273},
        "readings": {"fp8": (0.001077571772477703, 0.11528701249856052,
                             0.5469759473353161),
                     "half_batch": (0.007505444740141641,
                                    0.21760756608576823,
                                    0.06259865642662493)}},
    "sage-products": {
        "weights": {
            "cls": (3.499536393324206, -0.061773039400577545),
            "l0_neigh": (-29.80029066238467, 0.0176470298320055),
            "l0_self": (18.973734675600184, -0.046069275587797165),
            "l1_neigh": (7.8300955106947185, 0.07161403447389603),
            "l1_self": (11.956151329875468, -0.058093633502721786),
            "l2_neigh": (-8.42664817828711, -0.09891408681869507),
            "l2_self": (20.807546920433083, -0.09710768610239029)},
        "batch": 1024, "flops": 65983217664,
        "losses": (3.8457577228546143, 3.844099760055542,
                   3.8539106845855713),
        "grad_norms": {"cls": 0.36425020467746705,
                       "cls_bias": 0.3667792602260977,
                       "l0_bias": 0.01926143465985911,
                       "l0_neigh": 0.08615140087947316,
                       "l0_self": 0.1649306170253724,
                       "l1_bias": 0.2754671824993994,
                       "l1_neigh": 0.2125566185687313,
                       "l1_self": 0.2660430681541633,
                       "l2_bias": 0.2588309461284727,
                       "l2_neigh": 0.24553729022771079,
                       "l2_self": 0.2573358627247803},
        "readings": {"fp8": (0.0016578950640704464, 0.33458526811179184,
                             0.6794140055596963),
                     "half_batch": (0.007238590456985514,
                                    0.24995774471374446,
                                    0.09604618487771084)}},
}
NUMBERS = ("loss_gap", "grad_norm_gap", "change_norm_gap")


@pytest.mark.parametrize("name", sorted(PINNED))
def test_graphsage_arch_reads_what_the_direct_calls_read(name):
    import jax

    import control
    import graphgen
    import reference
    pin = PINNED[name]
    cfg = dict(harness.load_json(os.path.join(BENCH, "configs",
                                              name + ".json")), **TINY)
    model = harness.arch(BENCH, cfg["arch"])
    p0 = jax.device_get(model.init_params(SEED, cfg, cfg["feat_dim"]))
    for k, v in p0.items():
        total, last = pin["weights"].get(k, (0.0, 0.0))
        v = np.asarray(v)
        assert v.dtype == np.float32
        assert float(v.astype(np.float64).sum()) == pytest.approx(
            total, rel=1e-9, abs=1e-12), k
        assert float(v.ravel()[-1]) == last, k
    assert model.matmul_flops(cfg, pin["batch"]) == pin["flops"]

    arrays = graphgen.make_graph(cfg)
    batches = control.batches(cfg, arrays, SEED, cfg["batch_size"])
    with jax.default_matmul_precision("highest"):
        losses, g0, _ = reference.train(p0, batches, cfg["optimizer"],
                                        model.loss_fn)
    assert losses == pytest.approx(pin["losses"], rel=1e-6)
    assert reference.leaf_norms(g0) == pytest.approx(pin["grad_norms"],
                                                     rel=1e-5)
    r = control.readings(cfg, model, arrays, SEED, cfg["batch_size"])
    for variant, want in pin["readings"].items():
        assert [r[variant][k] for k in NUMBERS] == pytest.approx(
            want, rel=1e-4), variant


def test_an_unknown_arch_is_refused_by_name():
    with pytest.raises(harness.Fail, match="'gat'"):
        harness.arch(BENCH, "gat")


def test_a_graphsage_config_with_another_aggregator_is_refused():
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         "sage-reddit.json"))
    model = harness.arch(BENCH, "graphsage")
    with pytest.raises(ValueError, match="pool"):
        model.model(dict(cfg, aggregator="pool"), 8)


@pytest.mark.parametrize("chips", [1, 4])
def test_the_batch_is_per_chip(chips):
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         "sage-products.json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "mesh4_hbm.json"))
    spec = harness.pipeline_dict(cfg, traffic, SEED, None, "", None, chips)
    assert spec["batch_size"] == 1024 * chips
    assert spec["backend"] == {"name": "isp"}


MESH_RUN = textwrap.dedent("""
    import json, os, sys, types
    bench, root, fault = sys.argv[1], sys.argv[2], sys.argv[3]
    sys.path[:0] = [bench, os.path.join(bench, "tests")]
    import harness
    if fault == "exchange_left_out":
        from repro.core import isp
        isp.lax = types.SimpleNamespace(psum=lambda x, axis_name: x)
    elif fault != "none":
        import pytest
        import test_bench_harness
        from repro import core
        test_bench_harness.FAULTS[fault][0](core, pytest.MonkeyPatch())
    out = harness.run_cell(root, "tiny-mesh4", %d, 0.5, False,
                           require_chip=False,
                           cache_dir=os.path.join(root, "cache"),
                           log=lambda s: None)
    print(json.dumps(out))
""" % SEED)
# A fault the four-chip cell can have, and the number that catches it.
MESH_FAULTS = {"exchange_left_out": "ids_mismatched",
               "half_batch": "grad_norm_gap",
               "state_unchanged": "change_norm_gap"}


@pytest.fixture(scope="module")
def mesh_root(tmp_path_factory):
    """A checkout with a tiny configuration of the products
    configuration's depth, and the cell ``tiny-mesh4`` on
    ``products-mesh4``'s traffic."""
    import shutil
    root = tmp_path_factory.mktemp("mesh4")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    man = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cfg = dict(harness.load_json(os.path.join(BENCH, "configs",
                                              "sage-products.json")),
               name="products-tiny", num_nodes=3000, avg_degree=8,
               max_degree=100, fanouts=[4, 3, 2], hidden=16, batch_size=8,
               graph_seed=5)
    (root / "bench" / "configs" / "products-tiny.json").write_text(
        json.dumps(cfg))
    man["configs"].append({"name": "products-tiny", "source": "test",
                           "file": "bench/configs/products-tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-mesh4",
                             "config": "products-tiny",
                             "traffic": "mesh4_hbm", "chips": 4,
                             "why": "test"})
    (root / "bench" / "limits" / "tiny-mesh4.json").write_text(
        json.dumps({"ids_mismatched": 0, "features_max_abs_diff": 0.0,
                    "labels_mismatched": 0, "loss_gap": 1e-3,
                    "grad_norm_gap": 0.08, "change_norm_gap": 0.05}))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def run_mesh(root, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", MESH_RUN, BENCH,
                           str(root), fault], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_four_chip_cell_runs_whole_on_four_virtual_devices(mesh_root):
    """``products-mesh4``'s traffic on a tiny graph: the isp mesh over
    four devices, 4 x 8 targets a step, checked against the reference and
    found correct."""
    out = run_mesh(mesh_root, "none")
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > harness.CHECK_STEPS
    assert set(out["metrics"]) == {"targets_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(MESH_FAULTS))
def test_a_broken_four_chip_run_is_not_correct(mesh_root, fault):
    """The same run with the chips' exchange left out (each chip keeps
    only the rows it owns), half the batch left out, or the state left
    unchanged: ``correct`` reads false, on the number that catches it."""
    out = run_mesh(mesh_root, fault)
    assert not out["correct"]
    c = out["checks"][MESH_FAULTS[fault]]
    assert c["value"] > c["limit"], out["checks"]
