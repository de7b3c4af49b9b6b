"""On-mesh collective-byte measurement: near-data ISP sampling vs the raw
edge-chunk fetch (the paper's 20x PCIe-traffic reduction, measured as ICI
collective bytes in lowered HLO on an 8-shard mesh).

Runs in a subprocess pinned to the CPU: it counts lowered HLO bytes on 8
virtual CPU devices, never claims an accelerator, and keeps the forced
8-device platform out of other benchmarks (they must see 1 device).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ISPGraph, load_dataset, partition_graph
from repro.launch.mesh import make_mesh
from repro.roofline.hlo_parse import analyze

g = load_dataset("reddit", large_scale=True)
mesh = make_mesh((8, 1), ("data", "model"))
eng = ISPGraph(partition_graph(g, 8), mesh)
M = 1024
targets = jnp.zeros((M,), jnp.int32)
max_deg = int(g.degrees().max())

with mesh:
    isp = jax.jit(lambda t, k: eng.sample_one_hop(t, 25, k)) \
        .lower(targets, jax.random.key(0)).compile()
    raw = jax.jit(lambda t: eng.fetch_edge_chunks(t, max_deg)) \
        .lower(targets).compile()

rows = {}
for name, c in (("isp_sample", isp), ("raw_chunk_fetch", raw)):
    costs = analyze(c.as_text(), 8)
    rows[name] = {"collective_bytes_per_chip": costs.link_bytes,
                  "counts": costs.collective_counts}
rows["reduction"] = (rows["raw_chunk_fetch"]["collective_bytes_per_chip"]
                     / max(rows["isp_sample"]["collective_bytes_per_chip"], 1))
rows["max_degree"] = max_deg
rows["fanout"] = 25
print("JSON:" + json.dumps(rows))
"""


def run():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=900)
    if r.returncode != 0:
        return [{"dataset": "reddit", "error": r.stderr[-500:]}]
    data = json.loads(r.stdout.split("JSON:")[1])
    return [{
        "dataset": "reddit",
        "isp_collective_bytes_per_chip":
            data["isp_sample"]["collective_bytes_per_chip"],
        "raw_fetch_collective_bytes_per_chip":
            data["raw_chunk_fetch"]["collective_bytes_per_chip"],
        "onmesh_transfer_reduction": data["reduction"],
        "max_degree": data["max_degree"], "fanout": data["fanout"],
        "paper_analogue": 20.0,
    }]
