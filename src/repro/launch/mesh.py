"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — required because the dry-run must
set XLA_FLAGS before any JAX initialization.

Every mesh has `Auto` axes: the sharding strategies place arrays with
`with_sharding_constraint`, which refuses the `Explicit` axes that
`jax.make_mesh` gives by default.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CPU-host sharding tests (8 fake devices)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
