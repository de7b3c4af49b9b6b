"""Operations and bytes the algorithm needs per training step, counted
from shapes alone, whatever implements them.

* ``step_matmul_flops``: GraphSAGE-mean's matmul FLOPs for forward and
  backward (3x forward).  Layer ``l`` applies two ``d_in x hidden``
  matmuls (self and aggregated neighbours) to every row of hops
  ``0 .. depth-1-l``; the classifier is ``batch x hidden x classes``.
  The fanout means and the optimizer are left out (not matmuls).
* ``sample_bytes``: k-hop neighbour sampling's least traffic: per
  target its id and its two CSR offsets, per sampled neighbour its
  random word, the list entry it picks and the id it writes (4 B each).
  The staging of whole edge blocks is the kernel's choice, not the
  algorithm's need, so it is not counted.
* ``gather_bytes``: a row gather's least traffic: each row read and
  written once at the logical width, plus its 4 B id (and 4 B slot
  lookup for a cached gather).
"""

from __future__ import annotations

import math


def hop_rows(batch: int, fanouts) -> list[int]:
    """Rows of each hop tensor: batch * f1 * ... * ft."""
    return [batch * math.prod(fanouts[:t]) for t in range(len(fanouts) + 1)]


def step_matmul_flops(batch: int, fanouts, feat_dim: int, hidden: int,
                      n_classes: int) -> int:
    rows = hop_rows(batch, fanouts)
    depth = len(fanouts)
    fwd, d_in = 0, feat_dim
    for l in range(depth):
        fwd += sum(2 * 2 * rows[t] * d_in * hidden
                   for t in range(depth - l))
        d_in = hidden
    fwd += 2 * batch * hidden * n_classes
    return 3 * fwd


def sample_bytes(targets: int, fanout: int) -> int:
    return targets * (4 + 8) + targets * fanout * (4 + 4 + 4)


def gather_bytes(rows: int, width: int, itemsize: int = 4,
                 cached: bool = False) -> int:
    return rows * (2 * width * itemsize + 4 + (4 if cached else 0))
