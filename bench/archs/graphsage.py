"""GraphSAGE-mean (Hamilton, Ying, Leskovec 2017, arXiv:1706.02216,
Algorithm 1 with the mean aggregator): the program's ``GraphSAGE`` and
its plain float32 reference.

The reference's layer ``l`` merges hop ``t+1`` into hop ``t`` for every
``t < depth - l``: h' = normalize(relu(h_t W_self + mean(h_{t+1}) W_neigh
+ b)); a linear classifier and softmax cross-entropy follow.  The
weights' leaves carry the program's names (``l<l>_self``,
``l<l>_neigh``, ``l<l>_bias``, ``cls``, ``cls_bias``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import flops
import reference


def _check(cfg: dict) -> None:
    if cfg["aggregator"] != "mean":
        raise ValueError(f"arch 'graphsage' has the mean aggregator's "
                         f"reference; the config asks for "
                         f"{cfg['aggregator']!r}")


def model(cfg: dict, feat_dim: int):
    """The program's GraphSAGE, built as a user builds it."""
    from repro.core import GNNConfig, GraphSAGE
    _check(cfg)
    return GraphSAGE(GNNConfig(feat_dim=feat_dim, hidden=cfg["hidden"],
                               n_classes=cfg["n_classes"],
                               fanouts=tuple(cfg["fanouts"]),
                               aggregator=cfg["aggregator"]))


def init_params(seed: int, cfg: dict, feat_dim: int) -> dict:
    _check(cfg)
    hidden, d_in = int(cfg["hidden"]), int(feat_dim)
    shapes = {}
    for l in range(len(cfg["fanouts"])):
        shapes[f"l{l}_self"] = (d_in, hidden)
        shapes[f"l{l}_neigh"] = (d_in, hidden)
        shapes[f"l{l}_bias"] = (hidden,)
        d_in = hidden
    shapes["cls"] = (d_in, int(cfg["n_classes"]))
    shapes["cls_bias"] = (int(cfg["n_classes"]),)
    return reference.init_weights(seed, shapes)


def loss_fn(params, hop_feats, labels, precision: str = "f32"):
    mm, rnd = reference.matmul, reference.rounded
    depth = len(hop_feats) - 1
    h = [rnd(jnp.asarray(x, jnp.float32), precision) for x in hop_feats]
    for l in range(depth):
        nxt = []
        for t in range(depth - l):
            agg = jnp.mean(h[t + 1], axis=-2)
            z = (mm(h[t], params[f"l{l}_self"], precision)
                 + mm(agg, params[f"l{l}_neigh"], precision)
                 + params[f"l{l}_bias"])
            z = jax.nn.relu(z)
            norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
            nxt.append(rnd(z / jnp.maximum(norm, 1e-6), precision))
        h = nxt
    logits = mm(h[0], params["cls"], precision) + params["cls_bias"]
    return reference.cross_entropy(logits, labels)


def matmul_flops(cfg: dict, batch: int) -> int:
    return flops.step_matmul_flops(batch, cfg["fanouts"], cfg["feat_dim"],
                                   cfg["hidden"], cfg["n_classes"])
