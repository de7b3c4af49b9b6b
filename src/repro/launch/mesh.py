"""Production mesh construction.

The production target is a TPU v5e pod slice: 256 chips arranged as a
(data=16, model=16) mesh per pod, and 2 pods (512 chips) for the multi-pod
configuration with a leading "pod" axis.  The "pod" axis is deliberately
kept pure-data-parallel so that the lowest-bandwidth link (inter-pod DCN)
carries only gradient all-reduce traffic (see DESIGN.md §3).

``make_production_mesh`` is a *function* (not a module-level constant) so
importing this module never touches jax device state — the dry-run script
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its
first jax import and only then builds the mesh.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Build the production mesh.

    Args:
      multi_pod: if True, build the 2-pod (2, 16, 16) mesh with axes
        ("pod", "data", "model"); otherwise the single-pod (16, 16) mesh
        with axes ("data", "model").
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """A mesh over the first prod(shape) devices.  The axes are Auto: the
    installed JAX makes Explicit axes by default, and the sharding rules
    here are constraints for Auto (GSPMD) propagation."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for smoke tests and CPU examples."""
    return make_mesh((1, 1), ("data", "model"))
