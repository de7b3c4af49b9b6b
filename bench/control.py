"""Readings that set the upper end of a cell's limits: the plain
reference put in the program's place, computed wrong on purpose, and
compared with the float32 reference by the run's own numbers.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Variants, each at the cell's own size on the cell's own batches (the
configuration's per-chip batch times the cell's chips), with the loss
of the configuration's arch (``bench/archs/<arch>.py``):

* ``fp8``: the reference in float8, the precision below the
  configuration's bfloat16 (the control);
* ``half_batch``: the reference's loss averaged over the first half of
  each batch (a planted fault).

A state left unchanged reads 1 on ``change_norm_gap`` by definition
and needs no run.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

import graphgen
import harness
import reference


def batches(cfg, arrays, seed, batch, steps=harness.CHECK_STEPS):
    out = []
    for i in range(steps):
        t = reference.targets(seed, i, int(cfg["num_nodes"]), batch)
        hops = reference.sample_khop(arrays["indptr"], arrays["indices"], t,
                                     cfg["fanouts"], seed, i)
        out.append(([arrays["features"][h] for h in hops],
                     arrays["labels"][t]))
    return out


def readings(cfg, model, arrays, seed, batch) -> dict:
    """Each variant's compared numbers against the float32 reference."""
    import jax

    p0 = jax.device_get(model.init_params(seed, cfg, int(cfg["feat_dim"])))
    bs = batches(cfg, arrays, seed, batch)
    opt, loss = cfg["optimizer"], model.loss_fn
    base = reference.train(p0, bs, opt, loss)
    half = [([f[:f.shape[0] // 2] for f in feats], labels[:labels.shape[0]
                                                          // 2])
            for feats, labels in bs]
    variants = {"fp8": reference.train(p0, bs, opt, loss, "fp8"),
                "half_batch": reference.train(p0, half, opt, loss)}
    return {name: gaps(p0, base, got) for name, got in variants.items()}


def gaps(p0, ref, got) -> dict:
    (rl, rg, rp), (gl, gg, gp) = ref, got
    gn = reference.leaf_norms(rg)
    median = float(np.median(list(gn.values())))
    moving = {k for k, v in gn.items() if v >= 1e-3 * median}
    change = lambda p: {k: np.asarray(p[k], np.float64)  # noqa: E731
                        - np.asarray(p0[k], np.float64) for k in p0}
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in zip(gl, rl)),
            "grad_norm_gap": reference.norm_gap(gg, rg),
            "change_norm_gap": reference.norm_gap(change(gp), change(rp),
                                                  keep=moving)}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    r = harness.resolve(harness.ROOT, args.workload)
    chips = int(r["cell"]["chips"])
    harness.check_devices(chips, True)
    cfg = r["config"]
    arrays = graphgen.load_or_make(cfg, os.path.join(harness.CACHE, "graphs"))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(cfg, r["arch"], arrays, seed,
                                     int(cfg["batch_size"]) * chips)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
