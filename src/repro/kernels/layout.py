"""The HBM row layout the data-plane kernels DMA from.

The TPU tiles a 2-D array's two minor dims by (8, 128), so a DMA of one
row of an ``(n, W)`` array is an unaligned slice that the compiler
refuses.  Stored as ``(n, 1, Wp)`` (``Wp`` = ``W`` rounded up to 128
lanes, zero-filled), the minor dims tile by (1, 128) and any single row
is one aligned DMA.  Feature tables, the feature-row cache and the
edge-block cache are held in this layout on the device.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

LANES = 128


def row_width(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles."""
    return -(-int(width) // LANES) * LANES


def to_rows(table):
    """(n, W) -> (n, 1, row_width(W)); numpy in, numpy out."""
    n, w = table.shape
    xp = np if isinstance(table, np.ndarray) else jnp
    return xp.pad(table, ((0, 0), (0, row_width(w) - w)))[:, None, :]


def from_rows(rows, width: int):
    """Inverse of ``to_rows``: (n, 1, Wp) -> (n, width)."""
    return rows[:, 0, :width]
