"""Logical-axis sharding rules (MaxText/t5x-style).

Every parameter and activation in the model zoo is annotated with *logical*
axis names.  A :class:`ShardingRules` table maps logical names to physical
mesh axes; swapping the table re-shards the whole model without touching
model code.  This is the layer the perf hillclimb iterates on.

Physical mesh axes (see ``repro.launch.mesh``):
  * ``pod``   — outer data-parallel axis crossing the pod boundary (DCN-class
                links in the paper's clusters; slowest).
  * ``data``  — intra-pod data-parallel / FSDP axis.
  * ``model`` — tensor-parallel axis (fast ICI neighbours).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[None, str, tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """Mapping from logical axis name -> physical mesh axis (or None)."""

    rules: dict[str, MeshAxis] = field(default_factory=dict)

    def spec(self, logical_axes: tuple[Optional[str], ...]) -> P:
        used: list[str] = []
        out: list[MeshAxis] = []
        for ax in logical_axes:
            phys = self.rules.get(ax) if ax is not None else None
            # A physical axis may appear at most once in a PartitionSpec.
            if phys is None:
                out.append(None)
                continue
            flat = (phys,) if isinstance(phys, str) else tuple(phys)
            flat = tuple(a for a in flat if a not in used)
            if not flat:
                out.append(None)
                continue
            used.extend(flat)
            out.append(flat[0] if len(flat) == 1 else flat)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def with_overrides(self, **kw: MeshAxis) -> "ShardingRules":
        merged = dict(self.rules)
        merged.update(kw)
        return ShardingRules(merged)


# ---------------------------------------------------------------------------
# Rule tables.
# ---------------------------------------------------------------------------
# FSDP x TP training layout: weights sharded over "data" on their
# d_model/embed axis (FSDP; XLA all-gathers per block inside the layer scan)
# and over "model" on their ff/heads axis (Megatron TP).  Weights are
# *replicated* across pods so forward-pass all-gathers never cross the slow
# pod boundary; only the per-step gradient all-reduce does (hierarchically).
# The batch is split over (pod, data).
TRAIN_RULES = ShardingRules(
    {
        # params
        "layers": None,
        "embed": "data",  # FSDP shard axis (intra-pod only)
        "q_heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "qkv_dim": "model",
        "ff": "model",
        "vocab": "model",
        "experts": None,
        "lru": "model",
        "lru_heads": "model",
        "conv": None,
        "rank": None,
        # activations
        "act_batch": ("pod", "data"),
        "act_seq": None,
        # Megatron-style sequence parallelism: the residual stream at layer
        # boundaries (== the activation saved for backward by remat) is
        # sequence-sharded over the TP axis; XLA inserts the all-gather /
        # reduce-scatter pair around each block.
        "act_res_seq": "model",
        "act_embed": None,
        "act_ff": "model",
        "act_heads": "model",
        "act_kv_heads": "model",
        "act_vocab": "model",
        "act_experts": None,
        "act_lru": "model",
        # kv cache
        "cache_batch": ("pod", "data"),
        "cache_seq": None,
    }
)

# Inference layout: weights stay sharded (model axis for TP; data used only
# to fit the very large models), KV caches are batch-sharded over data and
# sequence-sharded over the TP axis (flash-decoding style: XLA inserts the
# partial-softmax reduction over "model").  Sequence sharding also covers
# archs whose KV head count doesn't divide the TP axis (MQA, kv=8 on 16-way).
SERVE_RULES = TRAIN_RULES.with_overrides(
    act_batch=("pod", "data"),
    cache_batch=("pod", "data"),
    cache_seq="model",
)

LONG_CONTEXT_RULES = SERVE_RULES.with_overrides(
    act_batch=None,
    cache_batch=None,
    cache_seq=("pod", "data", "model"),  # batch=1: all axes on the sequence
)


# ---------------------------------------------------------------------------
# Mesh context: model code calls ``constrain`` on activations with logical
# names; inside jit under an active mesh context this becomes
# with_sharding_constraint, otherwise a no-op (CPU smoke tests).
# ---------------------------------------------------------------------------
class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[ShardingRules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh], rules: ShardingRules):
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_rules() -> Optional[ShardingRules]:
    return _CTX.rules


def spec_for(shape, axes, mesh: Mesh, rules: ShardingRules) -> P:
    """Shape-aware PartitionSpec: drops mesh axes that don't divide dims or
    are absent from the mesh (e.g. "pod" on a single-pod mesh)."""
    import numpy as np

    used: list[str] = []
    entries: list = []
    for dim, ax in zip(shape, axes):
        phys = rules.rules.get(ax) if ax is not None else None
        if phys is None:
            entries.append(None)
            continue
        flat = (phys,) if isinstance(phys, str) else tuple(phys)
        flat = tuple(a for a in flat if a in mesh.shape and a not in used)
        keep: list[str] = []
        prod = 1
        for a in flat:
            if dim % (prod * mesh.shape[a]) == 0:
                keep.append(a)
                prod *= mesh.shape[a]
        if not keep:
            entries.append(None)
            continue
        used.extend(keep)
        entries.append(keep[0] if len(keep) == 1 else tuple(keep))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint if a mesh context is active."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    spec = spec_for(x.shape, tuple(logical_axes), _CTX.mesh, _CTX.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def named_sharding(mesh: Mesh, rules: ShardingRules, axes: tuple[Optional[str], ...]) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(axes))
