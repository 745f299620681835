"""Pallas TPU flash attention: causal / local / chunked, with GQA.

TPU-native design (DESIGN.md §3: adapt, don't port):
  * grid = (batch, q_head, q_blocks, kv_blocks); the kv axis is the minor
    (sequential) grid dimension, so the online-softmax state lives in VMEM
    scratch across kv steps — no HBM round-trips for (m, l, acc);
  * BlockSpec tiles are MXU-aligned (block_q x d_head and block_k x d_head
    with d_head padded to 128 by the caller if needed);
  * causal/local/chunked block *skipping* happens at the grid level via
    ``pl.when`` — fully-masked (q_block, kv_block) pairs issue no MXU work,
    which the blockwise-jnp dry-run path cannot do (its rectangular scan
    carries ~2x causal overcompute; see EXPERIMENTS.md §Perf);
  * only the tiles that the mask's edge crosses (the causal diagonal, the
    window's far edge, a chunk edge) build the mask: a *full* tile, every
    pair of which attends, runs no iota, compare or ``where``
    (``block_class`` sorts the tiles);
  * the MXU takes q, k, v and the probabilities in the inputs' own dtype
    and accumulates in f32; the softmax state (m, l, acc), ``exp`` and the
    scale stay f32;
  * the row state m and l is kept as (block_q, 128), the same value in
    every lane, so that widening it over a tile's columns or over acc
    repeats whole vregs instead of broadcasting a (block_q, 1) column
    across lanes in every tile;
  * GQA is expressed in the index maps: kv head = q head // group size, so
    no KV replication is materialized.

``flash_attention`` here is the TPU execution path behind
``repro.kernels.ops.flash_attention``; the pure-jnp oracle lives in
``ref.py`` and the interpret=True equivalence tests in
``tests/test_kernels.py``.

Backward pass: ``flash_attention`` carries an explicit ``custom_vjp``.  The
forward is this Pallas kernel, which also emits the per-row log-sum-exp;
the backward is *not* a Pallas kernel — it is the blockwise-jnp flash
backward ``repro.kernels.ops._flash_bwd_impl``, which recomputes each
block's probabilities from (q, k, lse) and never materializes S x S.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _flash_bwd_impl

NEG_INF = -1e30
LANES = 128   # m and l are kept as (block_q, LANES), every lane alike


def block_class(q_start, k_start, block_q: int, block_k: int, causal: bool,
                window: int, chunk: int):
    """Sort the (q block, kv block) tile at (q_start, k_start) by the mask.

    Returns ``(run, full)``: ``run`` is false where no (q, k) pair of the
    tile attends (*skipped*), ``full`` true where every pair does (*full*);
    a tile with ``run`` and not ``full`` is *masked*, as the mask's edge
    crosses it.  ``full`` is exact; ``run`` may keep a tile that a chunk
    edge and the causal or window edge mask together.  Takes Python ints
    (giving bools) or traced scalars alike.
    """
    lo = q_start - (k_start + block_k - 1)   # least q - k in the tile
    hi = q_start + block_q - 1 - k_start      # greatest q - k
    run = full = True
    if causal:
        run &= hi >= 0
        full &= lo >= 0
    if window > 0:
        run &= lo < window
        full &= hi < window
    if chunk > 0:
        q0, q1 = q_start // chunk, (q_start + block_q - 1) // chunk
        k0, k1 = k_start // chunk, (k_start + block_k - 1) // chunk
        run &= (q1 >= k0) & (q0 <= k1)
        full &= (q0 == q1) & (k0 == k1) & (q0 == k0)
    return run, full


def _widen(x, n: int):
    """A lane-replicated (rows, LANES) state as (rows, n): whole vregs
    repeated where n is a multiple of LANES, with no lane broadcast."""
    if n % LANES == 0:
        return pltpu.repeat(x, n // LANES, 1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, chunk: int,
            softcap: float, block_q: int, block_k: int, n_kv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k
    run, full = block_class(q_start, k_start, block_q, block_k, causal,
                            window, chunk)

    def _update(masked: bool):
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= q_pos >= k_pos
            if window > 0:
                mask &= (q_pos - k_pos) < window
            if chunk > 0:
                mask &= (q_pos // chunk) == (k_pos // chunk)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, LANES), every lane alike
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _widen(m_new, block_k))
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _widen(corr, acc_scr.shape[1]) + pv
        m_scr[...] = m_new

    pl.when(full)(functools.partial(_update, masked=False))
    if not isinstance(full, bool):   # some tiles are crossed by the mask
        pl.when(jnp.logical_and(run, jnp.logical_not(full)))(
            functools.partial(_update, masked=True))

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / _widen(l, acc_scr.shape[1])).astype(
            o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, :1]


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 0,
    softcap: float = 0.0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable flash attention: Pallas forward, blockwise-jnp
    backward (see the module docstring).  ``interpret`` is for tests."""
    return _flash(q, k, v, causal, window, chunk, softcap, block_q, block_k,
                  interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, chunk, softcap, block_q, block_k,
           interpret):
    return _forward(q, k, v, causal, window, chunk, softcap, block_q,
                    block_k, interpret)[0]


def _flash_fwd(q, k, v, causal, window, chunk, softcap, block_q, block_k,
               interpret):
    o, lse = _forward(q, k, v, causal, window, chunk, softcap, block_q,
                      block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, chunk, softcap, block_q, block_k, interpret,
               res, do):
    q, k, v, o, lse = res
    B, Sq, H, _ = q.shape
    KV = k.shape[2]
    # kernel lse is (B, H, Sq, 1), head h = kv * G + g
    lse = lse.reshape(B, KV, H // KV, Sq)
    return _flash_bwd_impl(q, k, v, o, lse, do, causal=causal, window=window,
                           chunk=chunk, softcap=softcap, q_offset=0,
                           block_q=block_q, block_k=block_k,
                           seed_carries=bool(jax.typeof(q).vma))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _forward(q, k, v, causal, window, chunk, softcap, block_q, block_k,
             interpret):
    """The Pallas kernel: returns (o (B, Sq, H, D), lse (B, H, Sq, 1))."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    while Sq % block_q:
        block_q //= 2
    while Sk % block_k:
        block_k //= 2
    nq, nk = Sq // block_q, Sk // block_k

    qt = q.transpose(0, 2, 1, 3)  # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)  # (B, KV, Sk, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(D), causal=causal, window=window,
        chunk=chunk, softcap=softcap, block_q=block_q, block_k=block_k,
        n_kv=nk)

    # under shard_map the outputs vary over every axis an input varies over
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, k, v)))
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse

