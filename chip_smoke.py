"""Smoke run of the GraphSAGE data plane on a TPU chip.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the isp mesh only

Drives the training path a user runs (``build_pipeline`` ->
``build_train_step`` -> ``train_loop``) at the model's full width on the
``reddit --large-scale`` graph (8192 nodes, 602-wide features), generated
from ``--seed``: ``GNNConfig``'s own widths (hidden 256, fanouts
(25, 10)), batch 512, random initial weights.

One chip:
  (a) kernels   — each data-plane Pallas kernel, compiled (its lowered
                  text holds a ``tpu_custom_call``), equal bit for bit to
                  ``kernels/ref.py`` on the same chip;
  (b) pallas    — in memory; losses finite and bit-identical to the
                  ``isp`` backend on the same chip;
  (c) outofcore — ``benchmarks/specs/train_pallas_outofcore.json`` at the
                  same widths (disk store, 200-row feature cache, 64-block
                  edge cache); losses bit-identical to (b);
  (d) ispstore  — (c) with ``store.mode='isp'``: the storage process is
                  spawned while this process holds the chip; losses
                  bit-identical to (c).
Four chips (``--chips 4``): the ``isp`` backend with the graph partitioned
4 ways and the batch data-parallel; every partition on its own device;
minibatches bit-identical to the pallas kernels' on device 0; losses
within ``LOSS_RTOL`` of the same backend on one chip.

Each phase prints one line: compile seconds, steps, a wall-clock steps/s
(a smoke figure, not a benchmark) and the device's ``peak_bytes_in_use``.
The last line is the JSON verdict.  Any failure raises and exits
non-zero; without a TPU the script exits before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SPEC_OUTOFCORE = os.path.join(REPO, "benchmarks", "specs",
                              "train_pallas_outofcore.json")
DATASET = "reddit"
BATCH = 512
FANOUTS = (25, 10)
STEPS = 6
# 4-way data parallelism reduces the loss mean and the gradients over the
# batch in another order than one device does, so float32 sums differ in
# their last bits; AdamW's first steps move each weight by about the
# learning rate times the sign of its gradient, so a gradient that is
# rounding noise can flip, and later losses drift (9.3e-6 after 6 steps
# at these widths on 4 virtual CPU devices).  The bound leaves room for
# the chip's own reduction order, and stays far below the percent-level
# change of a loss averaged over one shard instead of the whole batch.
LOSS_RTOL = 1e-3

_compile_s = [0.0]


def _on_event_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke: {what}")


def report(phase: str, compile_s: float, steps: int, steps_per_s) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    rate = ("n/a" if steps_per_s is None else repr(steps_per_s))
    print(f"[smoke] phase={phase} compile_s={compile_s!r} steps={steps} "
          f"smoke_steps_per_s={rate} (wall clock; a smoke figure, not a "
          f"benchmark) peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def base_spec(backend: str, *, batch: int, fanouts, seed: int):
    from repro.core import BackendSpec, PipelineSpec, SamplerSpec, StoreSpec
    return PipelineSpec(backend=BackendSpec(name=backend),
                        sampler=SamplerSpec(family="khop", fanouts=fanouts),
                        store=StoreSpec(kind="mem"), batch_size=batch,
                        seed=seed)


def outofcore_spec(*, batch: int, fanouts, seed: int, mode: str = "local"):
    """The checked-in out-of-core spec at the smoke's widths; ``mode='isp'``
    serves its disk layout from a spawned storage process."""
    import dataclasses

    from repro.core import PipelineSpec, SamplerSpec
    from repro.core.config import IspSpec
    spec = PipelineSpec.load(SPEC_OUTOFCORE)
    store = dataclasses.replace(spec.store, mode=mode,
                                isp=IspSpec() if mode == "isp" else None)
    return spec.replace(sampler=SamplerSpec(family="khop", fanouts=fanouts),
                        batch_size=batch, seed=seed, store=store)


def train(spec, g, *, steps: int, mesh, seed: int):
    """Train GraphSAGE (``GNNConfig``'s widths) over ``spec``'s data plane
    through the normal entry points.  Returns (losses, compile seconds,
    steps/s over steps 2..N)."""
    import jax
    import jax.numpy as jnp

    from repro.core import (GNNConfig, GraphSAGE, build_pipeline,
                            build_train_step, train_loop)
    from repro.distributed.sharding import ShardingRules
    from repro.optim import adamw

    c0 = _compile_s[0]
    pipe = build_pipeline(spec, g, mesh=mesh)
    proc = getattr(pipe.store, "server_proc", None)
    try:
        gnn = GraphSAGE(GNNConfig(feat_dim=g.feat_dim,
                                  n_classes=int(g.labels.max()) + 1,
                                  fanouts=spec.effective_fanouts))
        opt = adamw(1e-3)
        step = build_train_step(pipe, gnn, opt, mesh,
                                ShardingRules.default())
        params = gnn.init(jax.random.key(seed))
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        losses, ends = [], []

        def on_step(i, state, metrics):
            losses.append(float(metrics["loss"]))
            ends.append(time.perf_counter())

        with mesh:
            train_loop(pipe, step, state, steps=steps, on_step=on_step)
        if proc is not None:
            check(proc.poll() is None,
                  "the storage process exited while training")
    finally:
        pipe.close()
    if proc is not None:
        check(proc.wait(timeout=60) == 0, "storage process exit code")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    rate = (steps - 1) / (ends[-1] - ends[0]) if steps > 1 else None
    return losses, _compile_s[0] - c0, rate


def phase_kernels(g, *, batch: int, fanouts, seed: int) -> int:
    """(a): every data-plane kernel compiled, against its jnp oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.layout import to_rows
    from repro.kernels.neighbor_sample import edge_block_count

    rng = np.random.default_rng(seed)
    indptr = jnp.asarray(g.indptr, jnp.int32)
    indices = jnp.asarray(g.indices, jnp.int32)
    table = jnp.asarray(g.features, jnp.float32)
    F = g.feat_dim
    maxd = int(g.degrees().max())
    block_e = ops.edge_block_size(maxd)
    nb = edge_block_count(g.num_edges, block_e)
    targets = jnp.asarray(rng.integers(0, g.num_nodes, batch * fanouts[0]),
                          jnp.int32)
    rand = jnp.asarray(rng.integers(0, 2**31 - 1,
                                    (targets.shape[0], fanouts[1])),
                       jnp.int32)
    # every edge block resident in a shuffled slot: the cached kernel
    # must read through the indirection, not the identity
    perm = rng.permutation(nb)
    padded = np.zeros(nb * block_e, np.int32)
    padded[:g.num_edges] = g.indices
    blocks = padded.reshape(nb, block_e)
    block_cache = np.empty_like(blocks)
    block_cache[perm] = blocks
    block_slots = jnp.asarray(np.append(perm, -1), jnp.int32)
    node_perm = rng.permutation(g.num_nodes)
    row_cache = np.empty_like(np.asarray(g.features, np.float32))
    row_cache[node_perm] = g.features
    slot_of = jnp.asarray(np.append(node_perm, -1), jnp.int32)
    hop_ids = jnp.asarray(rng.integers(0, g.num_nodes,
                                       batch * fanouts[0] * fanouts[1]),
                          jnp.int32)
    uniq = jnp.asarray(np.unique(np.asarray(hop_ids)), jnp.int32)
    means = jnp.asarray(rng.integers(0, g.num_nodes,
                                     (batch * fanouts[0], fanouts[1])),
                        jnp.int32)

    cases = {
        "neighbor_sample": (
            lambda ip, ix, t, r: ops.neighbor_sample(ip, ix, t, r,
                                                     max_degree=maxd),
            ref.neighbor_sample, (indptr, indices, targets, rand), 0),
        "neighbor_sample_cached": (
            lambda ip, sl, t, r, c: ops.neighbor_sample_cached(
                ip, to_rows(c), sl, t, r, block_e=block_e,
                max_block=nb - 2),
            lambda ip, sl, t, r, c: ref.neighbor_sample_cached(
                ip, sl, t, r, c, block_e=block_e, max_block=nb - 2),
            (indptr, block_slots, targets, rand, jnp.asarray(block_cache)),
            0),
        "feature_gather_rows": (
            lambda tb, ids: ops.feature_gather_rows(to_rows(tb), ids,
                                                    width=F),
            lambda tb, ids: ref.feature_gather_mean(tb, ids[:, None]),
            (table, hop_ids), 0),
        "feature_gather_cached": (
            lambda c, so, ids: ops.feature_gather_cached(to_rows(c), so, ids,
                                                         width=F),
            ref.feature_gather_cached,
            (jnp.asarray(row_cache), slot_of, uniq), 0),
        # a mean sums K rows; the kernel accumulates row by row while the
        # oracle's reduction may pair them otherwise: float32 rounding
        "feature_gather_mean": (
            lambda tb, ids: ops.feature_gather_mean(to_rows(tb), ids,
                                                    width=F),
            ref.feature_gather_mean, (table, means), 1e-5),
    }
    for name, (kernel, oracle, args, atol) in cases.items():
        text = jax.jit(kernel).lower(*args).as_text()
        check("tpu_custom_call" in text, f"{name}: no tpu_custom_call")
        got = np.asarray(jax.jit(kernel)(*args))
        want = np.asarray(jax.jit(oracle)(*args))
        check(got.shape == want.shape, f"{name}: shape {got.shape} "
              f"!= {want.shape}")
        if atol:
            err = float(np.max(np.abs(got - want)))
            check(err <= atol, f"{name}: max |error| {err} > {atol}")
            print(f"[smoke] {name}: {got.shape} within {atol} of the "
                  f"oracle (max |error| {err!r})", flush=True)
        else:
            check(np.array_equal(got, want), f"{name}: not bit-identical")
            print(f"[smoke] {name}: {got.shape} bit-identical to the "
                  "oracle", flush=True)
    return len(cases)


def one_chip(g, *, seed: int) -> None:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    c0 = _compile_s[0]
    n = phase_kernels(g, batch=BATCH, fanouts=FANOUTS, seed=seed)
    report("a-kernels", _compile_s[0] - c0, n, None)

    kw = dict(steps=STEPS, mesh=mesh, seed=seed)
    mem = dict(batch=BATCH, fanouts=FANOUTS, seed=seed)
    pallas, c, rate = train(base_spec("pallas", **mem), g, **kw)
    report("b-pallas-inmemory", c, STEPS, rate)
    isp, c, rate = train(base_spec("isp", **mem), g, **kw)
    report("b-isp-oracle", c, STEPS, rate)
    check(pallas == isp, f"pallas {pallas} != isp {isp}")
    print(f"[smoke] losses pallas == isp: {pallas}", flush=True)

    local, c, rate = train(outofcore_spec(**mem), g, **kw)
    report("c-pallas-outofcore", c, STEPS, rate)
    check(local == pallas, f"out-of-core {local} != in-memory {pallas}")
    print("[smoke] losses out-of-core == in-memory", flush=True)

    remote, c, rate = train(outofcore_spec(mode="isp", **mem), g, **kw)
    report("d-pallas-ispstore", c, STEPS, rate)
    check(remote == local, f"isp store {remote} != local store {local}")
    print("[smoke] losses isp store == local store", flush=True)


def four_chips(g, *, seed: int) -> None:
    import jax

    from repro.core import build_pipeline
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, have "
          f"{len(jax.devices())}")
    mesh4 = make_mesh((4, 1), ("data", "model"))
    mesh1 = make_mesh((1, 1), ("data", "model"))
    mem = dict(batch=BATCH, fanouts=FANOUTS, seed=seed)
    c0 = _compile_s[0]
    isp = build_pipeline(base_spec("isp", **mem), g, mesh=mesh4)
    kern = build_pipeline(base_spec("pallas", **mem), g, mesh=mesh1)
    try:
        eng = isp.loader.engine
        for name in ("indptr", "indices", "node_offset", "features",
                     "labels"):
            devs = getattr(eng, name).sharding.device_set
            check(len(devs) == 4, f"ISPGraph.{name} on {len(devs)} devices")
        print("[smoke] ISPGraph partitions: one per device, 4 devices",
              flush=True)
        for i in range(STEPS):
            a, b = isp.get_batch(i), kern.get_batch(i)
            for x, y in zip(a.hop_ids + a.hop_feats + [a.labels],
                            b.hop_ids + b.hop_feats + [b.labels]):
                check(np.array_equal(np.asarray(x), np.asarray(y)),
                      f"batch {i}: isp mesh != pallas on device 0")
        print(f"[smoke] {STEPS} minibatches: isp on 4 chips == pallas on "
              "device 0, ids and features bit-identical", flush=True)
    finally:
        isp.close()
        kern.close()
    report("mesh-minibatches", _compile_s[0] - c0, STEPS, None)

    kw = dict(steps=STEPS, seed=seed)
    four, c, rate = train(base_spec("isp", **mem), g, mesh=mesh4, **kw)
    report("mesh-isp-4chips", c, STEPS, rate)
    one, c, rate = train(base_spec("isp", **mem), g, mesh=mesh1, **kw)
    report("mesh-isp-1chip", c, STEPS, rate)
    err = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    check(err <= LOSS_RTOL, f"4-chip losses {four} vs 1-chip {one}: "
          f"relative error {err} > {LOSS_RTOL}")
    print(f"[smoke] losses 4 chips {four} vs 1 chip {one}: max relative "
          f"error {err!r} <= {LOSS_RTOL}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core import load_dataset
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    check(ops.kernels_enabled(), "Pallas kernels are disabled")
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    t0 = time.perf_counter()
    g = load_dataset(DATASET, large_scale=True, seed=args.seed)
    print(f"[smoke] {g.name}: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"{g.feat_dim}-wide features, made in "
          f"{time.perf_counter() - t0:.1f} s; batch {BATCH}, fanouts "
          f"{FANOUTS}, {STEPS} steps per phase; device {dev.device_kind}",
          flush=True)
    if args.chips == 4:
        four_chips(g, seed=args.seed)
    else:
        one_chip(g, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
