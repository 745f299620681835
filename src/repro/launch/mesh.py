"""Production mesh construction.

Target system: TPU v5e-class pods.  One pod = 256 chips arranged as a
``(data=16, model=16)`` mesh; the multi-pod configuration stacks a leading
``pod`` axis (2 pods = 512 chips) whose traffic crosses the slower
inter-pod interconnect (the paper's spine/DCN level).

Defined as functions — importing this module never touches jax device
state, so tests see the single CPU device unless they opt in.
"""
from __future__ import annotations

import jax
import numpy as np

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh_named(name: str) -> jax.sharding.Mesh:
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    raise ValueError(f"unknown mesh {name!r} (want single|multi)")


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def device_count_required(name: str) -> int:
    return int(np.prod(MULTI_POD if name == "multi" else SINGLE_POD))
