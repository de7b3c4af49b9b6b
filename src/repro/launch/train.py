"""End-to-end training driver: LM architectures and the SmartSAGE GNN.

Usage (CPU-scale; full-scale shapes are exercised by the dry-run):

  # GNN with near-data (ISP) subgraph generation on a 4-shard mesh:
  PYTHONPATH=src python -m repro.launch.train --arch graphsage \
      --dataset reddit --steps 100 --devices 4

  # Any assigned LM arch, reduced config:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --reduced --steps 50

Fault tolerance: checkpoints are written atomically every
``--ckpt-every`` steps (async), training auto-resumes from the latest
checkpoint in ``--ckpt-dir``, and batches are pure functions of the step
counter, so a killed-and-restarted run reproduces the uninterrupted loss
trajectory (tested in tests/test_train_integration.py).
"""

from __future__ import annotations

import argparse
import math
import time


def _parse():
    from repro.core.config import add_pipeline_args
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="graphsage")
    # the whole data-plane surface (--backend/--sampler/--fanouts/--batch/
    # --prefetch/--graph-store/--cache-*/--device-cache-*/
    # --edge-cache-blocks/--storage-engine/--spec/...) is generated from
    # the PipelineSpec field table — one definition shared with
    # benchmarks/bench_backends.py
    add_pipeline_args(ap, overrides={"backend": "isp"})
    ap.add_argument("--dataset", default="reddit")
    ap.add_argument("--large-scale", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced same-family LM config (CPU)")
    ap.add_argument("--devices", type=int, default=1,
                    help="devices in the mesh: accelerator chips (the run "
                         "fails if fewer exist), or virtual host devices "
                         "when the platform is the CPU")
    ap.add_argument("--mesh", default=None,
                    help="mesh shape, e.g. 4x1 (default: devices x 1)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(error if none exists); without --spec, the data "
                         "plane is rebuilt from the pipeline_spec embedded "
                         "in the checkpoint manifest, so the resumed run's "
                         "batches are bit-identical to the original's")
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir (where would the checkpoint "
                 "come from?)")
    from repro.core.config import (fill_pipeline_flag_defaults,
                                   spec_from_args)
    if args.arch == "graphsage":
        try:
            args.pipeline_spec = spec_from_args(args)
        except ValueError as e:
            ap.error(str(e))
    # resolve "not given" sentinels for code that reads flags directly
    # (the LM path's --batch); must run after the spec is assembled
    fill_pipeline_flag_defaults(args)
    return args


def main():
    args = _parse()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    enable_compile_cache()
    if args.devices > 1:
        # read by the CPU backend only: on an accelerator the mesh is
        # built from its real devices
        jax.config.update("jax_num_cpu_devices", args.devices)
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
    else:
        dims = (args.devices, 1)
    devices = jax.devices()
    if len(devices) < math.prod(dims):
        raise SystemExit(f"[train] mesh {dims} needs {math.prod(dims)} "
                         f"devices; {devices[0].platform} has "
                         f"{len(devices)}")
    mesh = make_mesh(dims, ("data", "model"))

    if args.arch == "graphsage":
        run_gnn(args, mesh)
    else:
        run_lm(args, mesh)


def run_gnn(args, mesh):
    import jax
    import jax.numpy as jnp

    from repro import checkpoint as ckpt
    from repro import obs
    from repro.core import (GNNConfig, GraphSAGE, build_pipeline,
                            build_train_step, load_dataset, train_loop)
    from repro.distributed.sharding import ShardingRules
    from repro.optim import adamw

    spec = args.pipeline_spec
    if args.resume:
        if ckpt.latest_step(args.ckpt_dir) is None:
            raise SystemExit(
                f"[train] --resume: no checkpoints in {args.ckpt_dir}")
        if not getattr(args, "spec", None):
            manifest = ckpt.read_manifest(args.ckpt_dir)
            if "pipeline_spec" in manifest:
                from repro.core.config import PipelineSpec
                spec = PipelineSpec.from_dict(manifest["pipeline_spec"])
                print("[train] --resume: data plane restored from the "
                      "checkpoint manifest's pipeline_spec")
    fanouts = spec.effective_fanouts
    g = load_dataset(args.dataset, large_scale=args.large_scale)
    pipe = build_pipeline(spec, g, mesh=mesh)
    try:
        for note in pipe.notes:
            print(f"[train] note: {note}")
        print(f"[train] {g.name}: {g.num_nodes} nodes {g.num_edges} edges, "
              f"{pipe.describe()}")
        store = pipe.store
        if store is not None and getattr(store, "kind", None) == "isp":
            c = store.client
            print(f"[train] graph store: in-storage processing service at "
                  f"{c.kind}:{c.address} (pid "
                  f"{store.server_proc.pid if store.server_proc else '-'}, "
                  f"window={c.window}, block {store.block_bytes} B) — "
                  "sample+gather pushed down to the storage process")
        elif store is not None:
            print(f"[train] graph store: disk at {store.path} "
                  f"({store.nbytes_on_disk() / 2**20:.1f} MB on disk, "
                  f"page cache {store.cache_blocks} x {store.block_bytes} B "
                  f"= {store.cache_blocks * store.block_bytes / 2**20:.1f} "
                  f"MB, policy={store.policy}, "
                  f"lock_shards={store.lock_shards})")

        cfg = GNNConfig(feat_dim=g.feat_dim, hidden=args.hidden,
                        n_classes=int(g.labels.max()) + 1, fanouts=fanouts)
        gnn = GraphSAGE(cfg)
        rules = ShardingRules.default()
        opt = adamw(args.lr)
        step_fn = build_train_step(pipe, gnn, opt, mesh, rules)

        state = {"params": gnn.init(jax.random.key(0)),
                 "opt": None, "step": jnp.zeros((), jnp.int32)}
        state["opt"] = opt.init(state["params"])
        start = 0
        saver = None
        if args.ckpt_dir:
            # every checkpoint manifest records the exact data-plane spec
            # that produced it
            saver = ckpt.AsyncSaver(
                args.ckpt_dir,
                manifest_extra={"pipeline_spec": spec.to_dict()})
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                state, start = ckpt.restore(args.ckpt_dir)
                start = int(start)
                print(f"[train] resumed from step {start}")

        def on_step(i, state, metrics):
            if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
                m = {k: float(v) for k, v in metrics.items()}
                print(f"  step {i+1:5d} loss={m['loss']:.4f} "
                      f"acc={m['acc']:.3f} |g|={m['grad_norm']:.3f}")
            if saver and (i + 1) % args.ckpt_every == 0:
                saver.save_async(i + 1, state)

        with mesh:
            state, stats = train_loop(pipe, step_fn, state,
                                      steps=args.steps, start=start,
                                      on_step=on_step)
        if saver:
            saver.save_async(args.steps, state)
            saver.wait()
        loader_stats = pipe.stats()
        print(f"[train] {stats.steps} steps in {stats.wall_s:.1f}s "
              f"({stats.steps_per_s:.2f} steps/s, consumer idle "
              f"{stats.idle_fraction:.1%}) loader={loader_stats}")
        # the per-epoch summary table, rendered from the canonical
        # metric namespace (repro.obs.names)
        metrics = obs.names.flatten_stats(loader_stats)
        metrics.update(obs.names.train_metrics(
            stats.steps, stats.idle_s, stats.busy_s, stats.steps_per_s,
            stats.idle_fraction))
        print(obs.epoch_summary(metrics))
        if pipe.obs is not None:
            if spec.obs.trace_path:
                print(f"[obs] trace -> {spec.obs.trace_path} "
                      "(open at https://ui.perfetto.dev)")
            if spec.obs.metrics_path:
                print(f"[obs] metrics snapshots -> {spec.obs.metrics_path}")
        for kind, noun in (("devcache", "rows"), ("edgecache", "blocks")):
            dc = loader_stats.get(kind)
            if dc:
                print(f"[train] device {kind}: {dc['capacity_rows']} {noun} "
                      f"({dc['policy']}, {dc['pinned_rows']} pinned), "
                      f"hits={dc['hits']} misses={dc['misses']} "
                      f"evictions={dc['evictions']} "
                      f"({dc['bytes_uploaded'] / 2**20:.1f} MB uploaded)")
        if store is not None:
            io = store.io_counters()
            print(f"[train] disk-store I/O: {io['requests']} requests, "
                  f"{io['block_fetches']} block fetches "
                  f"({io['bytes_fetched'] / 2**20:.1f} MB from disk), "
                  f"cache hits={io['hits']} misses={io['misses']} "
                  f"evictions={io['evictions']}")
            if getattr(store, "kind", None) == "isp":
                w = store.isp_counters()
                print(f"[train] isp wire: {w['requests']} commands, "
                      f"{w['bytes_tx'] / 2**20:.2f} MB tx / "
                      f"{w['bytes_rx'] / 2**20:.2f} MB rx "
                      f"(vs {io['bytes_fetched'] / 2**20:.1f} MB read from "
                      f"flash server-side), disconnects={w['disconnects']} "
                      f"reconnects={w['reconnects']}")
            if pipe.engine is not None and hasattr(pipe.engine, "report"):
                print(f"[train] measured-vs-simulated: {pipe.engine.report()}")
    finally:
        # a failed or interrupted run must not leak fds or the (possibly
        # multi-GB) temp copy of the graph
        pipe.close()


def run_lm(args, mesh):
    import jax
    import jax.numpy as jnp

    from repro import checkpoint as ckpt
    from repro.data import TokenPipeline
    from repro.distributed.sharding import ShardingRules, named_sharding
    from repro.models.registry import get_config
    from repro.models.transformer import LM
    from repro.optim import adamw, warmup_cosine
    from repro.train.steps import build_train_step, init_train_state

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = LM(cfg)
    rules = ShardingRules.default()
    print(f"[train] {cfg.name}: {model.param_count()/1e6:.2f}M params "
          f"({model.active_param_count()/1e6:.2f}M active)")

    opt = adamw(warmup_cosine(args.lr, 10, args.steps))
    step_fn = jax.jit(build_train_step(model, opt, mesh, rules,
                                       microbatches=args.microbatches),
                      donate_argnums=0)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         global_batch=args.batch)

    with mesh:
        state = init_train_state(model, opt, jax.random.key(0))
        start = 0
        saver = None
        if args.resume and ckpt.latest_step(args.ckpt_dir) is None:
            raise SystemExit(
                f"[train] --resume: no checkpoints in {args.ckpt_dir}")
        if args.ckpt_dir:
            saver = ckpt.AsyncSaver(args.ckpt_dir)
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                shardings = jax.tree.map(lambda x: x.sharding, state)
                state, start = ckpt.restore(args.ckpt_dir,
                                            shardings=shardings)
                start = int(start)
                print(f"[train] resumed from step {start}")

        tok_shard = named_sharding(("batch", "seq"), rules, mesh)
        t0 = time.time()
        for i in range(start, args.steps):
            batch = pipe.jax_batch(i, {"tokens": tok_shard,
                                       "labels": tok_shard})
            state, metrics = step_fn(state, batch)
            if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
                m = {k: float(v) for k, v in metrics.items()}
                print(f"  step {i+1:5d} loss={m['loss']:.4f} "
                      f"|g|={m['grad_norm']:.3f} lr={m['lr']:.2e}")
            if saver and (i + 1) % args.ckpt_every == 0:
                saver.save_async(i + 1, state)
        if saver:
            saver.save_async(args.steps, state)
            saver.wait()
        dt = time.time() - t0
        tokens = (args.steps - start) * args.batch * args.seq_len
        print(f"[train] {args.steps - start} steps in {dt:.1f}s "
              f"({tokens / max(dt, 1e-9):.0f} tok/s)")


if __name__ == "__main__":
    main()
