"""The benchmark's graph generator: a configuration's published shape,
made from its ``graph_seed``.

Degrees follow a discrete power law ``P(d) ~ d**-alpha`` on
``[1, max_degree]``, with ``alpha`` solved so that the mean is the
configuration's ``avg_degree``; the sampled degrees are then nudged so
that they sum to exactly ``num_nodes * avg_degree`` list entries.
Neighbour lists come from the configuration model: every list entry is
a stub, stubs are paired at random, and each stub's entry names the node
that owns its partner.  So a node appears in other nodes' lists exactly
as often as its own degree, as in an undirected graph (self-loops and
repeated pairs are kept, as the configuration model makes them).

Features are standard normal float32 rows; labels are uniform over the
classes.  Everything is a pure function of the configuration, so a graph
written to the cache once is the same graph every later run would make.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

GRAPH_KEYS = ("num_nodes", "avg_degree", "max_degree", "feat_dim",
              "n_classes", "graph_seed")


def solve_alpha(mean: float, max_degree: int) -> float:
    """Exponent of the power law on [1, max_degree] whose mean is
    ``mean`` (bisection; the mean falls as alpha grows)."""
    d = np.arange(1, max_degree + 1, dtype=np.float64)
    if not 1.0 < mean < (max_degree + 1) / 2:
        raise ValueError(f"mean degree {mean} is not reachable by a "
                         f"decreasing power law on [1, {max_degree}]")
    lo, hi = -1.0, 8.0
    for _ in range(200):
        a = (lo + hi) / 2
        p = d ** -a
        if (p * d).sum() / p.sum() > mean:
            lo = a
        else:
            hi = a
    return (lo + hi) / 2


def degrees(num_nodes: int, num_entries: int, max_degree: int,
            rng: np.random.Generator) -> np.ndarray:
    """``num_nodes`` degrees in [1, max_degree] summing to
    ``num_entries``, drawn from the solved power law."""
    alpha = solve_alpha(num_entries / num_nodes, max_degree)
    d = np.arange(1, max_degree + 1, dtype=np.float64)
    p = d ** -alpha
    deg = rng.choice(max_degree, size=num_nodes, p=p / p.sum()) + 1
    deg = deg.astype(np.int64)
    while True:
        diff = num_entries - int(deg.sum())
        if diff == 0:
            return deg
        step = 1 if diff > 0 else -1
        room = deg < max_degree if step > 0 else deg > 1
        pick = rng.choice(np.flatnonzero(room), size=min(abs(diff),
                                                        int(room.sum())),
                          replace=False)
        deg[pick] += step


def make_graph(cfg: dict) -> dict:
    """The arrays of ``cfg``'s graph: indptr (N+1,) int64, indices (E,)
    int32, features (N, F) float32, labels (N,) int32."""
    n = int(cfg["num_nodes"])
    e = int(round(n * float(cfg["avg_degree"])))
    e += e % 2                          # stubs pair up
    rng = np.random.default_rng(int(cfg["graph_seed"]))
    deg = degrees(n, e, int(cfg["max_degree"]), rng)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    owner = np.repeat(np.arange(n, dtype=np.int32), deg)
    order = rng.permutation(e)
    partner = np.empty(e, np.int64)
    partner[order[0::2]] = order[1::2]
    partner[order[1::2]] = order[0::2]
    del order
    indices = owner[partner]
    del partner, owner
    features = rng.standard_normal((n, int(cfg["feat_dim"])),
                                   dtype=np.float32)
    labels = rng.integers(0, int(cfg["n_classes"]), n).astype(np.int32)
    return {"indptr": indptr, "indices": indices, "features": features,
            "labels": labels}


def graph_key(cfg: dict) -> str:
    """Cache key of ``cfg``'s graph: a hash of the keys that shape it."""
    shape = {k: cfg[k] for k in GRAPH_KEYS}
    blob = json.dumps(shape, sort_keys=True).encode()
    return f"{cfg['name']}-{hashlib.sha256(blob).hexdigest()[:12]}"


def load_or_make(cfg: dict, cache_dir: str) -> dict:
    """``cfg``'s graph from ``cache_dir``, made and written there on the
    first call.  Files are written under a temporary name and renamed,
    so a run cut short never leaves half a graph behind."""
    path = os.path.join(cache_dir, graph_key(cfg))
    names = ("indptr", "indices", "features", "labels")
    if all(os.path.exists(os.path.join(path, f"{k}.npy")) for k in names):
        return {k: np.load(os.path.join(path, f"{k}.npy")) for k in names}
    arrays = make_graph(cfg)
    os.makedirs(path, exist_ok=True)
    for k in names:
        tmp = os.path.join(path, f"{k}.tmp.npy")
        np.save(tmp, arrays[k])
        os.replace(tmp, os.path.join(path, f"{k}.npy"))
    return arrays
