"""The plain reference: what every model's float32 training shares,
independent of the program under test.  It imports nothing of the
program.  Each architecture's own forward pass and loss live in
``bench/archs/<arch>.py``, written from its published equations.

What it reproduces, given the seed:

* the target stream: batch ``i`` draws ``batch`` node ids uniformly with
  replacement from ``numpy.random.default_rng(seed + i)``;
* the k-hop sample: hop ``h`` of batch ``i`` draws its random words from
  ``jax.random.randint(fold_in(fold_in(key(seed), i), h), shape, 0,
  2**31 - 1)``, and a node of degree ``d > 0`` picks the entry
  ``indptr[node] + word % d`` of its neighbour list (a node of degree 0
  picks itself);
* the features and labels of the sampled ids, read from the graph;
* the weights, from the seed's weight stream (``init_weights``);
* three steps of training: the arch's loss and its gradients, global-norm
  clipping and AdamW, all in float32 at ``highest`` matmul precision.

``precision="fp8"`` rounds every matmul operand and every layer's input
to float8 (e4m3) and back (``rounded``, ``matmul``), the control that
must fail the comparison.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_STREAM = 0x7FFFFFFF          # fold of the seed's key that makes weights


def targets(seed: int, idx: int, num_nodes: int, batch: int) -> np.ndarray:
    return np.random.default_rng(seed + idx).integers(
        0, num_nodes, batch).astype(np.int32)


def sample_khop(indptr: np.ndarray, indices: np.ndarray, tgt: np.ndarray,
                fanouts, seed: int, idx: int) -> list[np.ndarray]:
    """Per-hop sampled ids [(B,), (B, f1), (B, f1, f2), ...]."""
    key = jax.random.fold_in(jax.random.key(seed), idx)
    hops = [np.asarray(tgt, np.int64)]
    for h, f in enumerate(fanouts):
        frontier = hops[-1]
        words = np.asarray(jax.random.randint(
            jax.random.fold_in(key, h), frontier.shape + (f,), 0,
            2**31 - 1)).astype(np.int64)
        start = indptr[frontier]
        deg = indptr[frontier + 1] - start
        pos = start[..., None] + words % np.maximum(deg, 1)[..., None]
        pos = np.minimum(pos, indices.shape[0] - 1)
        pick = indices[pos].astype(np.int64)
        hops.append(np.where(deg[..., None] > 0, pick, frontier[..., None]))
    return [h.astype(np.int32) for h in hops]


def init_weights(seed: int, shapes: dict) -> dict:
    """The benchmark's weights, made on the device in one jitted call:
    leaf ``i`` of ``shapes`` in sorted order is normal from
    ``fold_in(weight key, i)`` with standard deviation 1/sqrt(fan_in) where
    it is a matrix, and 0 where it is a vector."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                out[name] = w / np.float32(np.sqrt(shape[0]))
        return out

    return make(jax.random.fold_in(jax.random.key(seed), WEIGHT_STREAM))


def rounded(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def matmul(x, w, precision: str):
    return jnp.einsum("...f,fg->...g", rounded(x, precision),
                      rounded(w, precision),
                      precision=jax.lax.Precision.HIGHEST)


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy of (B, C) logits against (B,) labels."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def adamw_step(params, grads, m, v, step: int, opt: dict):
    """One AdamW update after global-norm clipping; returns the new
    params, moments and the clipped gradients."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = float(step + 1)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)

    def upd(p, m_, v_):
        delta = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t))
                                         + opt["eps"])
        return p - opt["lr"] * (delta + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


def train(params0: dict, batches, opt: dict, loss_fn,
          precision: str = "f32"):
    """Train ``len(batches)`` steps of the arch's ``loss_fn(params,
    hop_feats, labels, precision)`` from ``params0``.  ``batches`` is a
    list of (hop_feats, labels).  Returns (losses, first clipped
    gradient, final params), all on the host."""
    grad_fn = jax.jit(jax.value_and_grad(loss_fn), static_argnums=3)
    params = jax.tree.map(jnp.asarray, params0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, (feats, labels) in enumerate(batches):
        loss, grads = grad_fn(params, [jnp.asarray(f) for f in feats],
                              jnp.asarray(labels), precision)
        params, m, v, clipped = adamw_step(params, grads, m, v, i, opt)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(clipped)
    return losses, first, jax.device_get(params)


def leaf_norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's |norm(prog) - norm(ref)| over the larger of the
    reference leaf's norm and the median leaf norm."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    names = [k for k in rn if keep is None or k in keep]
    median = float(np.median([rn[k] for k in names]))
    return max(abs(pn[k] - rn[k]) / max(rn[k], median, 1e-30)
               for k in names)
