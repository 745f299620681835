"""Pallas TPU kernel for the RWKV-6 (Finch) WKV recurrence.

TPU adaptation: the (D_k x D_v) per-head state matrix stays resident in
VMEM across the *entire* sequence — the grid iterates (batch, head,
time-chunk) with the time axis minor/sequential, so state never round-trips
HBM between chunks (the GPU formulation re-loads state per thread-block).
Inside a chunk the recurrence is a short fori_loop of rank-1 updates; r/k/
v/w arrive as (chunk, D) VMEM tiles.  An incoming state (decode, or a
sequence split across calls) seeds the VMEM state at the first chunk.

out_t = r_t . (S + diag(u) k_t^T v_t);  S <- diag(w_t) S + k_t^T v_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_final_ref,
            s_scr, *, chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    u = u_ref[0].astype(jnp.float32)  # (D, 1): bonus over the key dim

    one = jnp.ones((1, 1), jnp.float32)

    def col(x):  # (1, D) row -> (D, 1) column, on the MXU
        return jax.lax.dot_general(x, one, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def step(t, _):
        # rows (1, D): 2-D slices keep every vector on the (8, 128) tiling
        r_t = r_ref[0, 0, pl.ds(t, 1), :]
        k_t = k_ref[0, 0, pl.ds(t, 1), :]
        v_t = v_ref[0, 0, pl.ds(t, 1), :]
        w_t = w_ref[0, 0, pl.ds(t, 1), :]
        kv = col(k_t) * v_t                       # (D, D) rank-1
        s = s_scr[...]
        out = jnp.dot(r_t, s + u * kv, preferred_element_type=jnp.float32)
        o_ref[0, 0, pl.ds(t, 1), :] = out
        s_scr[...] = col(w_t) * s + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(c == n_chunks - 1)
    def _emit_state():
        s_final_ref[0, 0] = s_scr[...]


def wkv6(
    r: jax.Array,  # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0, 1)
    u: jax.Array,  # (H, D)
    state: jax.Array | None = None,  # (B, H, D, D) f32 (zeros if None)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, S, H, D = r.shape
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    # f32 tiles: the step loop reads one row at a dynamic offset, which the
    # TPU compiler accepts only for unpacked (32-bit) sublanes
    rt, kt, vt, wt = (x.astype(jnp.float32).transpose(0, 2, 1, 3)
                      for x in (r, k, v, w))  # (B, H, S, D)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    o, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            # (H, D, 1) so the block's last two dims are the array's
            pl.BlockSpec((1, D, 1), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(H, D, 1), state.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3).astype(r.dtype), s_final
