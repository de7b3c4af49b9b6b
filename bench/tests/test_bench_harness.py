"""The harness on the CPU at a tiny size: it finds a cell that is added
as files alone, drives a whole run of it, and decides ``correct`` false
when the timed path is broken underneath; a model enters as files alone
too (``sagepool``: an arch module the test writes, over the program's
pool aggregator); the lower-precision control fails the same limits; a
run without a chip reports nothing."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

SEED = 2**31 + 977          # past 32 signed bits, as the driver's are
TINY = {"name": "sage-tiny", "num_nodes": 3000, "avg_degree": 8,
        "max_degree": 100, "feat_dim": 24, "n_classes": 5,
        "fanouts": [4, 3], "hidden": 16, "batch_size": 16,
        "graph_seed": 5}
# The tiny cell's own limits, read as the real cells' are: its bfloat16
# program reads loss gaps near 2e-4 and gradient and change gaps of a
# few 1e-3 on the CPU; the planted faults read far above these.
LIMITS = {"ids_mismatched": 0, "features_max_abs_diff": 0.0,
          "labels_mismatched": 0, "loss_gap": 1e-3, "grad_norm_gap": 0.08,
          "change_norm_gap": 0.05}

# A model added as files alone: GraphSAGE with the max-pool aggregator
# (Hamilton et al. 2017, eq. 3), the program's ``aggregator="pool"``,
# and a float32 reference of its own.
SAGEPOOL = '''
import jax
import jax.numpy as jnp

import reference


def model(cfg, feat_dim):
    from repro.core import GNNConfig, GraphSAGE
    return GraphSAGE(GNNConfig(feat_dim=feat_dim, hidden=cfg["hidden"],
                               n_classes=cfg["n_classes"],
                               fanouts=tuple(cfg["fanouts"]),
                               aggregator="pool"))


def init_params(seed, cfg, feat_dim):
    shapes, d_in, hid = {}, feat_dim, cfg["hidden"]
    for l in range(len(cfg["fanouts"])):
        shapes.update({f"l{l}_self": (d_in, hid), f"l{l}_neigh": (d_in, hid),
                       f"l{l}_bias": (hid,), f"l{l}_pool_w": (d_in, d_in),
                       f"l{l}_pool_b": (d_in,)})
        d_in = hid
    shapes.update({"cls": (d_in, cfg["n_classes"]),
                   "cls_bias": (cfg["n_classes"],)})
    return reference.init_weights(seed, shapes)


def loss_fn(params, hop_feats, labels, precision="f32"):
    hi = jax.lax.Precision.HIGHEST
    mm = lambda x, w: jnp.einsum("...f,fg->...g", x, w, precision=hi)
    h = [jnp.asarray(x, jnp.float32) for x in hop_feats]
    for l in range(len(h) - 1):
        nxt = []
        for t in range(len(h) - 1):
            pool = jax.nn.relu(mm(h[t + 1], params[f"l{l}_pool_w"])
                               + params[f"l{l}_pool_b"]).max(axis=-2)
            z = jax.nn.relu(mm(h[t], params[f"l{l}_self"])
                            + mm(pool, params[f"l{l}_neigh"])
                            + params[f"l{l}_bias"])
            norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
            nxt.append(z / jnp.maximum(norm, 1e-6))
        h = nxt
    return reference.cross_entropy(mm(h[0], params["cls"])
                                   + params["cls_bias"], labels)


def matmul_flops(cfg, batch):
    rows, fwd, d_in = [batch], 0, cfg["feat_dim"]
    for f in cfg["fanouts"]:
        rows.append(rows[-1] * f)
    for l in range(len(cfg["fanouts"])):
        live = len(cfg["fanouts"]) - l
        fwd += sum(2 * rows[t + 1] * d_in * d_in
                   + 4 * rows[t] * d_in * cfg["hidden"] for t in range(live))
        d_in = cfg["hidden"]
    return 3 * (fwd + 2 * batch * d_in * cfg["n_classes"])
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with one more configuration, cell and limits, each a
    new file; ``BENCHMARK.json`` gains the entries that name them."""
    import jax
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_compilation_cache_max_size",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    man = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cfg = dict(harness.load_json(os.path.join(
        BENCH, "configs", "sage-reddit.json")), **TINY)
    (root / "bench" / "configs" / "sage-tiny.json").write_text(
        json.dumps(cfg))
    man["configs"].append({"name": "sage-tiny", "source": "test",
                           "file": "bench/configs/sage-tiny.json",
                           "reduced": [], "why": "test"})
    (root / "bench" / "archs" / "sagepool.py").write_text(SAGEPOOL)
    (root / "bench" / "configs" / "sagepool-tiny.json").write_text(
        json.dumps(dict(cfg, name="sagepool-tiny", arch="sagepool",
                        aggregator="pool")))
    man["configs"].append({"name": "sagepool-tiny", "source": "test",
                           "file": "bench/configs/sagepool-tiny.json",
                           "reduced": [], "why": "test"})
    for name, config, traffic in [
            ("tiny-pallas_hbm", "sage-tiny", "pallas_hbm"),
            ("tiny-pallas_ooc", "sage-tiny", "pallas_ooc"),
            ("tiny-sagepool", "sagepool-tiny", "pallas_hbm")]:
        man["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": 1,
                                 "why": "test"})
        (root / "bench" / "limits" / f"{name}.json").write_text(
            json.dumps(LIMITS))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    yield str(root)
    for k, v in saved.items():
        jax.config.update(k, v)


def run(root, workload="tiny-pallas_hbm", seconds=0.3):
    return harness.run_cell(root, workload, SEED, seconds, False,
                            require_chip=False,
                            cache_dir=os.path.join(root, "cache"),
                            log=lambda s: None)


def test_harness_finds_a_cell_added_as_files(root):
    r = harness.resolve(root, "tiny-pallas_hbm")
    assert r["config"]["num_nodes"] == 3000
    assert r["traffic"]["pipeline"]["backend"]["name"] == "pallas"
    assert r["limits"] == LIMITS
    assert [m["name"] for m in r["end_to_end"]] == ["targets_per_s",
                                                    "setup_s"]
    # every per-layer metric lists its cells, so a new cell reads none
    # until an entry of its own names it
    assert r["per_layer"] == []
    names = [m["name"] for m in harness.resolve(root,
                                                "reddit-hbm")["per_layer"]]
    assert "feature_cache_hit_rate" not in names and "compile_s" in names
    for m in harness.load_json(os.path.join(root,
                                            "BENCHMARK.json"))["per_layer"]:
        assert callable(harness.reader(r["metrics_dir"], m["name"]))
    with pytest.raises(harness.Fail):
        harness.resolve(root, "no-such-cell")


@pytest.mark.parametrize("workload", ["tiny-pallas_hbm", "tiny-pallas_ooc"])
def test_a_sound_run_is_correct(root, workload):
    out = run(root, workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"targets_per_s", "setup_s"}
    assert out["metrics"]["targets_per_s"]["value"] > 0
    assert out["attempted"] > harness.CHECK_STEPS and out["failed"] == 0
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def _state_unchanged(core, monkeypatch):
    import jax
    import jax.numpy as jnp
    real = core.build_train_step

    def build(*a, **k):
        step = real(*a, **k)

        def broken(state, mb):
            keep = jax.tree.map(jnp.copy, state)
            _, metrics = step(state, mb)
            return keep, metrics
        return broken
    monkeypatch.setattr(core, "build_train_step", build)


def _half_batch(core, monkeypatch):
    real = core.build_train_step

    def build(*a, **k):
        step = real(*a, **k)

        def broken(state, mb):
            h = int(np.asarray(mb.targets).shape[0]) // 2
            half = core.Minibatch(
                targets=mb.targets[:h], hop_ids=[x[:h] for x in mb.hop_ids],
                hop_feats=[x[:h] for x in mb.hop_feats], labels=mb.labels[:h])
            return step(state, half)
        return broken
    monkeypatch.setattr(core, "build_train_step", build)


def _altered(field):
    def plant(core, monkeypatch):
        real = core.build_pipeline

        def build(*a, **k):
            pipe = real(*a, **k)
            get = pipe.get_batch

            def broken(idx):
                mb = get(idx)
                if field == "ids":
                    mb.hop_ids[-1] = mb.hop_ids[-1].at[0, 0, 0].add(1)
                elif field == "feats":
                    mb.hop_feats[-1] = mb.hop_feats[-1].at[0, 0, 0, 0].add(
                        0.5)
                else:
                    mb.labels = mb.labels.at[0].add(1)
                return mb
            pipe.get_batch = broken
            return pipe
        monkeypatch.setattr(core, "build_pipeline", build)
    return plant


FAULTS = {"state_unchanged": (_state_unchanged, "change_norm_gap"),
          "half_batch": (_half_batch, "grad_norm_gap"),
          "id_altered": (_altered("ids"), "ids_mismatched"),
          "feature_altered": (_altered("feats"), "features_max_abs_diff"),
          "label_altered": (_altered("labels"), "labels_mismatched")}


@pytest.mark.parametrize("fault", [None, "half_batch", "state_unchanged"])
def test_a_model_added_as_files_runs_and_is_checked(root, monkeypatch,
                                                    fault):
    """``sagepool`` enters with a config, an arch module, limits and
    manifest entries: every file the repository's ``bench/`` has is
    unchanged in the checkout.  A sound run is correct; a run with its
    state left unchanged or half its batch left out is not."""
    import filecmp
    for d, _, files in os.walk(BENCH):
        if "__pycache__" in d or os.sep + "tests" in d or ".cache" in d:
            continue
        for f in files:
            mine = os.path.join(root, "bench", os.path.relpath(d, BENCH), f)
            assert filecmp.cmp(os.path.join(d, f), mine, shallow=False), f
    r = harness.resolve(root, "tiny-sagepool")
    assert r["arch"].__name__ == "bench_arch_sagepool"
    if fault is None:
        out = run(root, "tiny-sagepool")
        assert out["correct"], out["checks"]
        assert out["attempted"] > harness.CHECK_STEPS
        return
    from repro import core
    plant, number = FAULTS[fault]
    plant(core, monkeypatch)
    out = run(root, "tiny-sagepool")
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro import core
    plant, number = FAULTS[fault]
    plant(core, monkeypatch)
    out = run(root)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


def test_the_lower_precision_control_fails_the_limits():
    """The reference itself, computed in float8 in the program's place,
    at reddit's widths and fanouts on a small graph: its readings fall
    outside the limits, as does the half-batch fault's."""
    import control
    import graphgen
    cfg = dict(harness.load_json(os.path.join(
        BENCH, "configs", "sage-reddit.json")), num_nodes=5000,
        batch_size=32, graph_seed=5)
    r = control.readings(cfg, harness.arch(BENCH, "graphsage"),
                         graphgen.make_graph(cfg), SEED, 32)
    limits = harness.load_json(os.path.join(BENCH, "limits",
                                            "reddit-hbm.json"))
    for variant in ("fp8", "half_batch"):
        assert any(r[variant][k] > limits[k] for k in r[variant]), r


def test_the_control_fails_the_four_chip_cells_limits():
    """The same at products' widths and depth, against the limits of
    ``products-mesh4``, whose fp8 control reads nearest its sound runs."""
    import control
    import graphgen
    cfg = dict(harness.load_json(os.path.join(
        BENCH, "configs", "sage-products.json")), num_nodes=5000,
        batch_size=8, graph_seed=5)
    r = control.readings(cfg, harness.arch(BENCH, "graphsage"),
                         graphgen.make_graph(cfg), SEED, 4 * 8)
    limits = harness.load_json(os.path.join(BENCH, "limits",
                                            "products-mesh4.json"))
    for variant in ("fp8", "half_batch"):
        assert any(r[variant][k] > limits[k] for k in r[variant]), r


def test_without_a_chip_the_run_reports_nothing(root):
    with pytest.raises(harness.Fail):
        harness.run_cell(root, "tiny-pallas_hbm", SEED, 0.1, False,
                         cache_dir=os.path.join(root, "cache"))
