"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t, with data-dependent a_t.

TPU adaptation: width is tiled into lane-aligned blocks (the recurrence is
elementwise over width, so the grid parallelizes (batch, width-block) and
iterates time-chunks sequentially with the (block_w,) hidden state in VMEM.
Contrast with the associative-scan formulation used on the dry-run path
(ops.rglru): the parallel scan is O(S log S) elementwise work and
materializes two (B,S,W) intermediates; the kernel is O(S) with the state
in VMEM and is the preferred form once S*W no longer fits in HBM headroom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, la_ref, h0_ref, o_ref, hn_ref, h_scr, *,
            chunk: int, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    def step(t, _):
        # rows (1, bw) at a dynamic offset: f32 tiles (see ``rglru``)
        la = la_ref[0, pl.ds(t, 1), :]
        x = x_ref[0, pl.ds(t, 1), :]
        a = jnp.exp(la)
        b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * la), 1e-12)) * x
        h = a * h_scr[...] + b
        h_scr[...] = h
        o_ref[0, pl.ds(t, 1), :] = h
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(c == n_chunks - 1)
    def _emit():
        hn_ref[0] = h_scr[...]


def rglru(
    x: jax.Array,      # (B, S, W) gated input
    log_a: jax.Array,  # (B, S, W)
    h0: jax.Array | None = None,  # (B, W) f32
    *,
    chunk: int = 128,
    block_w: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, S, W = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    # f32 tiles: the step loop reads one row at a dynamic offset, which the
    # TPU compiler accepts only for unpacked (32-bit) sublanes; h0/hn are
    # (B, 1, W) so their blocks' last two dims are (1, block_w)
    xf = x.astype(jnp.float32)
    laf = log_a.astype(jnp.float32)
    h0 = h0.astype(jnp.float32).reshape(B, 1, W)
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk
    block_w = min(block_w, W)
    while W % block_w:
        block_w //= 2
    n_w = W // block_w

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    # grid: (batch * width-blocks) parallel, time sequential (minor)
    o, hn = pl.pallas_call(
        kernel,
        grid=(B * n_w, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w),
                         lambda bw, c, n_w=n_w: (bw // n_w, c, bw % n_w)),
            pl.BlockSpec((1, chunk, block_w),
                         lambda bw, c, n_w=n_w: (bw // n_w, c, bw % n_w)),
            pl.BlockSpec((1, 1, block_w),
                         lambda bw, c, n_w=n_w: (bw // n_w, 0, bw % n_w)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w),
                         lambda bw, c, n_w=n_w: (bw // n_w, c, bw % n_w)),
            pl.BlockSpec((1, 1, block_w),
                         lambda bw, c, n_w=n_w: (bw // n_w, 0, bw % n_w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(xf, laf, h0)
    return o.astype(x.dtype), hn.reshape(B, W)
