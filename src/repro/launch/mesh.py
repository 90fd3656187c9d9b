"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the model code places activations
    with ``with_sharding_constraint``, which only Auto axes accept."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
