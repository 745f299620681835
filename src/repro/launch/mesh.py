"""Small device meshes for multi-device CPU tests.

Defined as functions — importing this module never touches jax device
state, so tests see the single CPU device unless they opt in (a subprocess
with ``--xla_force_host_platform_device_count``).
"""
from __future__ import annotations

import jax


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))
