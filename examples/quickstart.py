"""Quickstart: SmartSAGE-on-TPU in ~60 lines.

Builds a Kronecker-expanded power-law graph and trains GraphSAGE through
the unified minibatch data plane: pick a data-preparation backend
(``host`` numpy pipeline, ``isp`` near-data mesh sampling, or ``pallas``
in-storage-style kernels) and every one feeds the same consumer with the
same ``Minibatch`` contract (the paper's backend comparison, live).

Run:  PYTHONPATH=src python examples/quickstart.py [backend]
"""

import sys

import jax
import jax.numpy as jnp

# 4 virtual devices when the platform is the CPU (the CPU backend alone
# reads this); on an accelerator the mesh spans its real chips
jax.config.update("jax_num_cpu_devices", 4)

from repro.core import (GNNConfig, GraphSAGE, build_train_step, load_dataset,
                        make_loader, train_loop)
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_mesh
from repro.optim import adamw

BACKEND = sys.argv[1] if len(sys.argv) > 1 else "isp"
FANOUTS = (10, 5)
BATCH = 64
STEPS = 30

# 1. A power-law graph, fractally expanded (Table I methodology).
graph = load_dataset("reddit", large_scale=False)
print(f"graph: {graph.num_nodes} nodes, {graph.num_edges} edges, "
      f"{graph.feat_dim}-d features")

# 2. Mesh + the chosen data-preparation backend.  For `isp` the cold graph
#    lives sharded over the 'data' axis — the TPU analogue of the SSD; the
#    other backends run single-device data preparation.
mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
loader = make_loader(BACKEND, graph, batch_size=BATCH, fanouts=FANOUTS,
                     mesh=mesh)
print(f"backend: {BACKEND}")

# 3. The shared GraphSAGE consumer: one jitted update step over whatever
#    Minibatch the backend produced (sample -> gather -> convolve -> AdamW).
gnn = GraphSAGE(GNNConfig(feat_dim=graph.feat_dim, hidden=128,
                          n_classes=int(graph.labels.max()) + 1,
                          fanouts=FANOUTS))
opt = adamw(1e-3)
rules = ShardingRules.default()
step = build_train_step(loader, gnn, opt, mesh, rules)

params = gnn.init(jax.random.key(0))
state = {"params": params, "opt": opt.init(params),
         "step": jnp.zeros((), jnp.int32)}


def log(i, state, m):
    if (i + 1) % 10 == 0:
        print(f"step {i+1:3d}  loss={float(m['loss']):.4f}  "
              f"acc={float(m['acc']):.3f}")


with mesh:
    state, stats = train_loop(loader, step, state, steps=STEPS, on_step=log)
loader.close()

print(f"{stats.steps_per_s:.2f} steps/s, consumer idle "
      f"{stats.idle_fraction:.1%}")
print("done — see examples/isp_vs_mmap.py for the storage-tier story")
