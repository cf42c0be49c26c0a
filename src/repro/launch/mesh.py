"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data parallelism and the work-exchange/failure domain (DESIGN §3).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever fits the local devices (tests / examples on CPU)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    """Axes that shard the batch: ('pod','data') when pod exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
