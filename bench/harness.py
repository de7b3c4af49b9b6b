"""The benchmark harness: one cell, one seed, one run.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything
else is found by name:

* its configuration, at the ``file`` the manifest gives it;
* its model, ``bench/archs/<arch>.py`` for the configuration's ``arch``:
  ``model(cfg, feat_dim)``, the program's network; ``init_params(seed,
  cfg, feat_dim)``, the benchmark's weights under the program's leaf
  names; ``loss_fn(params, hop_feats, labels, precision)``, the plain
  float32 reference loss; ``matmul_flops(cfg, batch)``, the matmul
  FLOPs of one step's forward and backward passes;
* its traffic, ``bench/traffic/<traffic>.json``: the data plane the job
  runs (the pipeline spec, with device caches given as shares of the
  graph), the warm-up steps and the traced steps;
* its limits, ``bench/limits/<workload>.json``: the limit of every number
  the correctness check compares;
* each per-layer metric, ``bench/metrics/<metric>.py``: a ``read(ctx)``
  that returns the number or None.

The configuration's ``batch_size`` is per chip: a step trains
``batch_size`` times the cell's ``chips`` targets.

A run drives the path a user runs: ``build_pipeline`` ->
``build_train_step`` -> ``train_loop``, in a closed loop (the next batch
is taken as soon as the step before it completes).  Set-up makes the
graph (or loads it from the checkout's cache), builds the pipeline and
the step, and drives the first ``CHECK_STEPS`` steps and the warm-up
through the window's own feed and call.  The window then runs for
``--seconds``.  Once it has closed and the program's state is freed,
the plain reference (``reference.py``) checks what the timed path made.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import graphgen
import peaks
import reference
import tracereduce

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")
CHECK_STEPS = 3                 # steps the reference follows
WINDOW_SAMPLES = 8              # window batch checked: one of the first 8
MISMATCH = 1e30                 # a compared number whose shapes differ


class Fail(Exception):
    """A run that cannot report: no chip, fewer chips than the cell asks
    for, a manifest that does not name the cell, or a configuration
    whose ``arch`` has no module."""


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> dict:
    """The cell ``workload`` of ``root``'s manifest, with its
    configuration, traffic, limits and metric lists."""
    man = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise Fail(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in man["configs"]}
    bench = os.path.join(root, "bench")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    cfg = load_json(os.path.join(root, confs[cell["config"]]["file"]))
    return {
        "cell": cell,
        "config": cfg,
        "arch": arch(bench, cfg["arch"]),
        "traffic": load_json(os.path.join(bench, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(bench, "limits",
                                         workload + ".json")),
        "end_to_end": [m for m in man["end_to_end"] if applies(m)],
        "per_layer": [m for m in man["per_layer"] if applies(m)],
        "metrics_dir": os.path.join(bench, "metrics"),
    }


def load_module(prefix: str, path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metrics_dir: str, name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    return load_module("bench_metric_",
                       os.path.join(metrics_dir, name + ".py")).read


def arch(bench: str, name: str):
    """The module ``bench/archs/<name>.py`` of a configuration's
    ``arch``."""
    path = os.path.join(bench, "archs", name + ".py")
    if not os.path.isfile(path):
        raise Fail(f"no model for arch {name!r}: {path} is missing")
    return load_module("bench_arch_", path)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Compiles:
    """Compile seconds and counts from ``jax.monitoring``: each backend
    compile, whether it compiled or loaded the program from the
    persistent cache (the event spans both)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class Feed:
    """The loader the step reads from: the pipeline, with each
    ``get_batch`` timed and named for the trace, and the batches the
    check needs kept."""

    def __init__(self, pipe, keep_host, keep_device):
        import jax
        self._jax = jax
        self.pipe = pipe
        self.fanouts = pipe.fanouts
        self.keep_host, self.keep_device = set(keep_host), set(keep_device)
        self.kept: dict[int, dict] = {}
        self.wait_s: list[float] = []
        self.uniq_rows: list[int] = []
        self.check_s = 0.0

    def get_batch(self, idx: int):
        jax = self._jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("get_batch"):
            mb = self.pipe.get_batch(idx)
        self.wait_s.append(time.perf_counter() - t0)
        if mb.trace is not None and mb.trace.subgraph_nodes is not None:
            self.uniq_rows.append(int(np.asarray(
                mb.trace.subgraph_nodes).size))
        if idx in self.keep_host:
            t1 = time.perf_counter()
            self.kept[idx] = {"ids": [np.asarray(h) for h in mb.hop_ids],
                              "feats": [np.asarray(f) for f in mb.hop_feats],
                              "labels": np.asarray(mb.labels)}
            self.check_s += time.perf_counter() - t1
        elif idx in self.keep_device:
            self.kept[idx] = {"ids": list(mb.hop_ids),
                              "feats": list(mb.hop_feats),
                              "labels": mb.labels}
        return mb


def pipeline_dict(cfg: dict, traffic: dict, seed: int, g, store_dir: str,
                  ops, chips: int) -> dict:
    """The traffic's pipeline spec, completed from the configuration:
    batch (per chip, times the chips), fanouts, seed, the store's
    directory, and device cache sizes from their shares of the graph."""
    from repro.kernels.neighbor_sample import edge_block_count

    spec = json.loads(json.dumps(traffic["pipeline"]))
    spec["batch_size"] = int(cfg["batch_size"]) * chips
    spec["seed"] = int(seed)
    spec["sampler"] = {"family": "khop", "fanouts": list(cfg["fanouts"])}
    if spec.get("store", {}).get("kind") == "disk":
        spec["store"]["path"] = store_dir
    tiers = []
    for t in spec.get("cache_tiers", []):
        t = dict(t)
        rows = t.pop("rows_share", 0.0)
        blocks = t.pop("edge_blocks_share", 0.0)
        if rows:
            t["rows"] = int(round(rows * g.num_nodes))
        if blocks:
            block_e = ops.edge_block_size(int(g.degrees().max()))
            t["edge_blocks"] = int(round(
                blocks * edge_block_count(g.num_edges, block_e)))
        if t.get("tier") == "device":
            t["arrays"] = ((["features"] if t.get("rows") else [])
                           + (["topology"] if t.get("edge_blocks") else []))
        tiers.append(t)
    spec["cache_tiers"] = tiers
    return spec


def check_devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise Fail(f"needs a TPU; JAX found {devs[0].platform!r}")
        peaks.peak(devs[0].device_kind)
    if len(devs) < chips:
        raise Fail(f"the cell asks for {chips} chips; JAX found "
                   f"{len(devs)}")
    return devs[:chips]


def set_compile_cache(cache_dir: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, with every program cached however small, fast or large
    (the isp backend's prepare program embeds the graph as constants)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             cache_dir: str = CACHE, t_start: float | None = None,
             log=print) -> dict:
    """One run of one cell.  Returns the result line's object, with the
    compared numbers last, under ``checks``."""
    t_start = time.perf_counter() if t_start is None else t_start
    r = resolve(root, workload)
    cell, cfg, traffic, model = (r["cell"], r["config"], r["traffic"],
                                 r["arch"])
    chips = int(cell["chips"])
    devs = check_devices(chips, require_chip)

    import jax
    import jax.numpy as jnp

    set_compile_cache(os.path.join(cache_dir, "jax"))
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    from repro import core
    from repro.core import CSRGraph, PipelineSpec
    from repro.distributed.sharding import ShardingRules
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw

    arrays = graphgen.load_or_make(cfg, os.path.join(cache_dir, "graphs"))
    g = CSRGraph(indptr=arrays["indptr"], indices=arrays["indices"],
                 features=arrays["features"], labels=arrays["labels"],
                 name=cfg["name"])
    store_dir = os.path.join(cache_dir, "stores", graphgen.graph_key(cfg))
    spec = PipelineSpec.from_dict(
        pipeline_dict(cfg, traffic, seed, g, store_dir, ops, chips))
    mesh = make_mesh((chips, 1), ("data", "model"))
    opt_cfg = cfg["optimizer"]
    window_pick = CHECK_STEPS + int(traffic["warmup_steps"]) \
        + seed % WINDOW_SAMPLES

    pipe = core.build_pipeline(spec, g, mesh=mesh)
    try:
        feed = Feed(pipe, keep_host=range(CHECK_STEPS),
                    keep_device=[window_pick])
        net = model.model(cfg, g.feat_dim)
        opt = adamw(opt_cfg["lr"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
                    eps=opt_cfg["eps"],
                    weight_decay=opt_cfg["weight_decay"],
                    max_grad_norm=opt_cfg["max_grad_norm"])
        step = core.build_train_step(feed, net, opt, mesh,
                                     ShardingRules.default())
        params = model.init_params(seed, cfg, g.feat_dim)
        params0 = jax.device_get(params)
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        del params

        losses: dict[int, object] = {}
        ends: list[float] = []
        probe: dict[str, object] = {}

        def on_step(i, st, metrics):
            ends.append(time.perf_counter())
            losses[i] = metrics["loss"]
            if i == 0 or i == CHECK_STEPS - 1:
                t1 = time.perf_counter()
                if i == 0:
                    probe["m0"] = jax.device_get(st["opt"]["m"])
                else:
                    probe["p3"] = jax.device_get(st["params"])
                feed.check_s += time.perf_counter() - t1

        def named_step(st, mb):
            with jax.profiler.TraceAnnotation("train_step"):
                return step(st, mb)

        def run_steps(first: int, n: int):
            nonlocal state
            with mesh, jax.profiler.TraceAnnotation("train_loop"):
                state, _ = core.train_loop(feed, named_step, state,
                                           start=first, steps=first + n,
                                           on_step=on_step)

        warm = CHECK_STEPS + int(traffic["warmup_steps"])
        run_steps(0, warm)
        jax.block_until_ready(state)
        counters0 = pipe.stats()
        compile_setup_s, compiles_setup = compiles.seconds, compiles.count
        n_wait0 = len(feed.wait_s)
        n_uniq0 = len(feed.uniq_rows)
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t_start - feed.check_s
        n0 = len(ends)
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="trace-", dir=cache_dir)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # host spans, no Python calls
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        i = warm
        with jax.profiler.TraceAnnotation("window"):
            while True:
                run_steps(i, 1)
                i += 1
                if trace and i - warm >= int(traffic["trace_steps"]):
                    break
                if not trace and time.perf_counter() - t_w0 >= seconds:
                    break
            jax.block_until_ready(state)
        t_w1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        window_s = t_w1 - t_w0
        steps = len(ends) - n0
        step_s = np.diff([t_w0] + ends[n0:])
        window_compiles = compiles.count - compiles_setup
        counters1 = pipe.stats()
        peak_bytes = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs)
        loss_values = {k: float(v) for k, v in losses.items()}
        kept = {k: jax.device_get(v) for k, v in feed.kept.items()}
        wait_s = feed.wait_s[n_wait0:]
        uniq_rows = feed.uniq_rows[n_uniq0:]
        del state, step, losses
    finally:
        pipe.close()
    del feed, pipe
    gc.collect()

    log(f"[bench] {workload} seed={seed}: setup {setup_s:.3f} s "
        f"(compiles {compile_setup_s:.3f} s in {compiles_setup}), window "
        f"{window_s:.3f} s, {steps} steps, compiles in window "
        f"{window_compiles}")

    ctx = Context(cfg=cfg, arch=model, traffic=traffic, chips=chips,
                  steps=steps,
                  window_s=window_s, batch=spec.batch_size,
                  compile_s=compile_setup_s, wait_s=wait_s,
                  uniq_rows=uniq_rows, counters0=counters0,
                  counters1=counters1,
                  peak=(peaks.peak(devs[0].device_kind) if require_chip
                        else None))
    result_metrics = {}
    breakdown = None
    busy = None
    if trace:
        tr = tracereduce.load(trace_dir)
        ctx.trace = tr
        ctx.ops = tr.ops_in(*tr.window())
        ctx.device = tracereduce.summary(tr, step_module=step_module)
        busy = ctx.device
        breakdown = {"device_ops": ctx.device["device_ops"],
                     "idle_gaps": ctx.device["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in r["per_layer"]:
            value = reader(r["metrics_dir"], m["name"])(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    else:
        e2e = {"targets_per_s": steps * spec.batch_size / window_s,
               "step_ms_p95": float(np.percentile(step_s, 95)) * 1e3,
               "setup_s": setup_s}
        for m in r["end_to_end"]:
            result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}

    checks = check(cfg, model, arrays, seed, params0, probe, loss_values,
                   kept, opt_cfg, r["limits"])
    failed = sum(not math.isfinite(v) for v in loss_values.values())
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and failed == 0
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": peak_bytes}
    if busy is not None:
        device["busy_s"] = busy["busy_s"]
        device["window_s"] = busy["window_s"]
    out = {"correct": bool(correct), "attempted": len(loss_values),
           "failed": failed, "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def step_module(module: str) -> bool:
    """Ops of the jitted train step (``build_train_step``'s ``step``)."""
    return module == "jit_step" or module.startswith("jit_step.")


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, **kw):
        self.trace = None
        self.device = None
        self.__dict__.update(kw)

    def delta(self, group: str, key: str):
        """Window change of a counter in ``pipe.stats()[group]``, or None
        where the pipeline has no such counter."""
        a = (self.counters0.get(group) or {}).get(key)
        b = (self.counters1.get(group) or {}).get(key)
        if a is None or b is None:
            return None
        return b - a

    def per_step(self, by_chip: dict) -> float:
        """Device seconds by chip -> ms per step on the slowest chip."""
        return max(by_chip.values()) * 1e3 / self.steps

    def roofline(self, kernel: str, bytes_per_step: float):
        """Percent of its roofline a kernel reached in the traced window:
        the least time a chip's share of its bytes needs at the chip's HBM
        bandwidth over the kernel's device time per step on the slowest
        chip.  None where the trace holds no such kernel."""
        if self.trace is None or self.peak is None or self.steps == 0:
            return None
        by_chip = tracereduce.ns_by_chip(
            o for o in self.ops if o.kernel == kernel)
        if not by_chip:
            return None
        seconds = max(by_chip.values()) / 1e9 / self.steps
        return 100.0 * bytes_per_step / self.chips \
            / self.peak["hbm_bytes_per_s"] / seconds


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def check(cfg, model, arrays, seed, params0, probe, losses, kept, opt_cfg,
          limits) -> dict:
    """Every compared number beside its limit.  Sampled ids, features
    and labels of the first ``CHECK_STEPS`` batches and of one window
    batch are compared exactly; the losses of the first steps, the first
    gradient as the optimizer got it and the parameters' change over the
    first steps against ``reference.train`` of the arch's ``loss_fn``."""
    import jax

    fanouts = tuple(cfg["fanouts"])
    n = int(cfg["num_nodes"])
    indptr, indices = arrays["indptr"], arrays["indices"]
    ids_bad = feats_bad = labels_bad = 0
    ref_batches = {}
    for idx in sorted(kept):
        got = kept[idx]
        batch = int(np.asarray(got["ids"][0]).shape[0])
        tgt = reference.targets(seed, idx, n, batch)
        hops = reference.sample_khop(indptr, indices, tgt, fanouts, seed,
                                     idx)
        feats = [arrays["features"][h] for h in hops]
        labels = arrays["labels"][tgt]
        for a, b in zip(got["ids"], hops):
            a = np.asarray(a)
            ids_bad += (int(np.sum(a != b)) if a.shape == b.shape
                        else b.size)
        for a, b in zip(got["feats"], feats):
            a = np.asarray(a)
            feats_bad = max(feats_bad, float(np.max(np.abs(a - b)))
                            if a.shape == b.shape else MISMATCH)
        a = np.asarray(got["labels"])
        labels_bad += (int(np.sum(a != labels)) if a.shape == labels.shape
                       else labels.size)
        if idx < CHECK_STEPS:
            ref_batches[idx] = (feats, labels)
        gc.collect()

    with jax.default_matmul_precision("highest"):
        ref_losses, ref_g0, ref_p3 = reference.train(
            params0, [ref_batches[i] for i in range(CHECK_STEPS)], opt_cfg,
            model.loss_fn)
    prog_g0 = {k: np.asarray(v) / (1 - opt_cfg["b1"])
               for k, v in probe["m0"].items()}
    gnorm = reference.leaf_norms(ref_g0)
    median = float(np.median(list(gnorm.values())))
    moving = {k for k, v in gnorm.items() if v >= 1e-3 * median}
    prog_change = {k: np.asarray(probe["p3"][k], np.float64)
                   - np.asarray(params0[k], np.float64) for k in params0}
    ref_change = {k: np.asarray(ref_p3[k], np.float64)
                  - np.asarray(params0[k], np.float64) for k in params0}
    loss_gap = max(abs(losses[i] - ref_losses[i]) / abs(ref_losses[i])
                   for i in range(CHECK_STEPS))
    values = {
        "ids_mismatched": ids_bad,
        "features_max_abs_diff": feats_bad,
        "labels_mismatched": labels_bad,
        "loss_gap": loss_gap,
        "grad_norm_gap": reference.norm_gap(prog_g0, ref_g0),
        "change_norm_gap": reference.norm_gap(prog_change, ref_change,
                                              keep=moving),
    }
    return {k: {"value": v if math.isfinite(v) else MISMATCH,
                "limit": limits[k]} for k, v in values.items()}


def main(argv=None, *, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
    except Fail as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[check] correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
