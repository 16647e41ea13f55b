"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state. The dry-run (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax import
so these meshes can be built on the CPU container.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally, as a 1D 'data' mesh (tests, examples)."""
    n = jax.device_count()
    return _auto_mesh((n,), ("data",))
