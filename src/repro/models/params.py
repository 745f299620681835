"""Parameter definitions: shape + dtype + logical axes + initializer.

A model is described by a pytree of :class:`ParamDef`.  From that single
source of truth we derive
  * concrete initialized parameters (smoke tests, real training),
  * abstract ``jax.ShapeDtypeStruct`` stand-ins (dry-run lowering),
  * per-parameter ``NamedSharding`` (via logical-axis rules).

Sharding resolution is *shape aware*: a mesh axis that does not evenly
divide the corresponding dimension is dropped (e.g. MQA's single KV head
cannot be sharded over a 16-way model axis; seamless' 256206 vocab is not
divisible by 16).  Dropped axes are recorded so the roofline report can
call them out.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.axes import ShardingRules

Initializer = Callable[[jax.Array, tuple[int, ...], Any], jax.Array]


@dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"  # normal | zeros | ones | scaled | custom
    init_scale: float = 1.0
    init_fn: Optional[Callable] = None  # used when init == "custom"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def normal_init(key, shape, dtype, scale):
    fan_in = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
    std = scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


_normal_init_jit = jax.jit(normal_init, static_argnums=(1, 2, 3))


def materialize(defs, seed: int = 0):
    """Initialize a pytree of ParamDef into concrete arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    root = jax.random.PRNGKey(seed)
    out = []
    for i, d in enumerate(leaves):
        key = jax.random.fold_in(root, i)
        if d.init == "zeros":
            arr = jnp.zeros(d.shape, d.dtype)
        elif d.init == "ones":
            arr = jnp.ones(d.shape, d.dtype)
        elif d.init == "custom":
            arr = d.init_fn(key, d.shape, d.dtype)  # type: ignore[misc]
        else:
            # jitted so the f32 draw fuses into the cast: a large bf16 leaf
            # never holds its f32 intermediates on the device
            arr = _normal_init_jit(key, d.shape, d.dtype, d.init_scale)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract(defs):
    """Pytree of ShapeDtypeStruct for .lower() without allocation."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


from repro.parallel.axes import spec_for  # shape-aware spec resolution


def shardings(defs, mesh: Mesh, rules: ShardingRules, dropped: Optional[list] = None):
    """Pytree of NamedSharding matching ``defs``."""
    return jax.tree_util.tree_map(
        lambda d: NamedSharding(mesh, spec_for(d.shape, d.axes, mesh, rules, dropped)),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def cast_defs(defs, dtype):
    """Re-type all float params (e.g. bf16 serving weights)."""
    import dataclasses as _dc

    return jax.tree_util.tree_map(
        lambda d: _dc.replace(d, dtype=dtype)
        if jnp.issubdtype(d.dtype, jnp.floating) else d,
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def count_params(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return int(sum(np.prod(d.shape) for d in leaves))


def param_bytes(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return int(sum(np.prod(d.shape) * np.dtype(d.dtype).itemsize for d in leaves))
