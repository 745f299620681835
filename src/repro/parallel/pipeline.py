"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

The production mesh for this paper's workloads is FSDP x TP (+pod DP) — the
paper's clusters ran Megatron/FSDP-style jobs — so pipelining is an optional
axis, exercised by tests and available for memory-constrained configs.

Implementation: ``shard_map`` over the ``stage`` axis; each stage holds
``n_layers / n_stages`` of the stacked layer weights; microbatches stream
through with ``jax.lax.ppermute`` handoffs.  Bubble fraction is
(S-1)/(M+S-1) for S stages and M microbatches, surfaced by
:func:`bubble_fraction` for the perf model.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_forward(
    layer_fn: Callable,  # (params_slice, x) -> x
    stage_params,        # stacked (n_stages, layers_per_stage, ...) pytree
    x: jax.Array,        # (n_microbatches, mb, seq, d) input microbatches
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run a GPipe forward pass across the ``stage`` mesh axis."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    steps = n_micro + n_stages - 1

    def per_stage(params_local, x_local):
        # params_local: (1, layers_per_stage, ...); x_local: microbatches on
        # stage 0, zeros elsewhere.
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage_id = jax.lax.axis_index(axis)

        def stage_apply(h):
            def body(hh, p_slice):
                return layer_fn(p_slice, hh), None
            out, _ = jax.lax.scan(body, h, params_local)
            return out

        mb_shape = x_local.shape[1:]
        state = jnp.zeros(mb_shape, x_local.dtype)  # in-flight activation
        outputs = jnp.zeros_like(x_local)
        # carries become device-varying inside the loop (stage_id use);
        # mark them as such up front for shard_map's vma typing
        state = jax.lax.pcast(state, (axis,), to="varying")
        outputs = jax.lax.pcast(outputs, (axis,), to="varying")

        def step(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (if any)
            take = jnp.clip(t, 0, n_micro - 1)
            injected = jax.lax.dynamic_index_in_dim(x_local, take, keepdims=False)
            state = jnp.where((stage_id == 0) & (t < n_micro), injected, state)
            state = stage_apply(state)
            # last stage emits microbatch t-(n_stages-1)
            emit_t = t - (n_stages - 1)
            emit = (stage_id == n_stages - 1) & (emit_t >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, state, jnp.clip(emit_t, 0, n_micro - 1), 0)
            outputs = jnp.where(emit, updated, outputs)
            # hand activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(state, axis, perm)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(step, (state, outputs), jnp.arange(steps))
        # only the last stage wrote outputs; replicate to all shards
        return jax.lax.psum(outputs, axis)

    return jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )(stage_params, x)
