"""Flash attention: causal / local / chunked, with GQA and a logit softcap.

One algorithm with two forwards and one backward under one VJP rule.  Both
forwards return ``(o (B, Sq, H, D), lse (B, H, Sq, 1))``, the per-row
log-sum-exp with head h = kv * G + g, and the backward reads either.

The Pallas TPU forward (``pallas_forward``):
  * grid = (batch, q_head, q_blocks, kv_blocks); the kv axis is the minor
    (sequential) grid dimension, so the online-softmax state lives in VMEM
    scratch across kv steps — no HBM round-trips for (m, l, acc);
  * BlockSpec tiles are MXU-aligned (block_q x d_head and block_k x d_head
    with d_head padded to 128 by the caller if needed);
  * causal/local/chunked block *skipping* happens at the grid level via
    ``pl.when`` — fully-masked (q_block, kv_block) pairs issue no MXU work
    (the blockwise scan below computes them and masks them out);
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

The blockwise-jnp forward (``blockwise_forward``) runs the same online
softmax as a scan over kv blocks, banded for a window or chunk that tiles
the sequence.  It serves the platforms without the kernel, and
cross-attention (Sq != Sk) on TPU.

The backward (``blockwise_backward``) is blockwise jnp for both: it
recomputes each block's probabilities from (q, k, lse) instead of letting
jax save every per-block residual of a forward scan, which would
materialize the S x S attention matrix in HBM.  Block indices are carried
as dynamic counters — not scan xs — so XLA cannot hoist the causal/window
masks into loop-invariant buffers.

``repro.kernels.ops.flash_attention`` chooses the forward; the pure-jnp
oracle lives in ``ref.py`` and the equivalence tests in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128   # m and l are kept as (block_q, LANES), every lane alike
BLOCK = 512   # q and kv block of both forwards and the backward


def _pick_block(s: int, target: int) -> int:
    """``target``, or less, halved until it divides ``s``."""
    b = min(s, target)
    while s % b:
        b //= 2
    return max(b, 1)


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
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable flash attention with the Pallas forward and the
    blockwise backward.  ``interpret`` is for tests."""
    return _flash(q, k, v, causal, window, chunk, softcap, block_q, block_k,
                  "interpret" if interpret else "pallas")


def blockwise_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 0,
    softcap: float = 0.0,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
) -> jax.Array:
    """Differentiable flash attention with the blockwise-jnp forward and
    backward."""
    return _flash(q, k, v, causal, window, chunk, softcap, block_q, block_k,
                  "blockwise")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, chunk, softcap, block_q, block_k,
           forward):
    return _forward(q, k, v, causal, window, chunk, softcap, block_q,
                    block_k, forward)[0]


def _forward(q, k, v, causal, window, chunk, softcap, block_q, block_k,
             forward):
    """``forward`` is "pallas", "interpret" (the kernel in interpret mode)
    or "blockwise"."""
    kw = dict(causal=causal, window=window, chunk=chunk, softcap=softcap,
              block_q=block_q, block_k=block_k)
    if forward == "blockwise":
        return blockwise_forward(q, k, v, **kw)
    return pallas_forward(q, k, v, interpret=forward == "interpret", **kw)


def _flash_fwd(q, k, v, causal, window, chunk, softcap, block_q, block_k,
               forward):
    o, lse = _forward(q, k, v, causal, window, chunk, softcap, block_q,
                      block_k, forward)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, chunk, softcap, block_q, block_k, forward,
               res, do):
    q, k, v, o, lse = res
    return blockwise_backward(q, k, v, o, lse, do, causal=causal,
                              window=window, chunk=chunk, softcap=softcap,
                              block_q=block_q, block_k=block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def pallas_forward(q, k, v, *, causal, window, chunk, softcap, block_q,
                   block_k, interpret=False):
    """The Pallas kernel: returns (o (B, Sq, H, D), lse (B, H, Sq, 1))."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    block_q = _pick_block(Sq, block_q)
    block_k = _pick_block(Sk, block_k)
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


def _plan(Sq, Sk, *, window, chunk, block_q, block_k):
    """Blocking plan of the blockwise paths: block sizes, the number of kv
    blocks each q block visits, and whether that band trails the q block
    (a window or chunk that tiles the sequence) or spans every kv block."""
    band = window if window > 0 else chunk
    if band > 0 and Sq == Sk and Sq >= band and Sq % band == 0:
        bq = _pick_block(band, block_q)
        bk = _pick_block(band, block_k)
        n_band = (band // bk) + (1 if window > 0 else 0)
        banded = True
    else:
        bq = _pick_block(Sq, block_q)
        bk = _pick_block(Sk, block_k)
        banded = False
        n_band = Sk // bk
    return bq, bk, n_band, banded


def _block_mask(q_pos, k_pos, valid, *, causal, window, chunk):
    m = jnp.broadcast_to(valid, (q_pos.shape[0], k_pos.shape[0]))
    if causal:
        m = m & (q_pos[:, None] >= k_pos[None, :])
    if window > 0:
        m = m & ((q_pos[:, None] - k_pos[None, :]) < window)
    if chunk > 0:
        m = m & ((q_pos[:, None] // chunk) == (k_pos[None, :] // chunk))
    return m


def blockwise_forward(q, k, v, *, causal, window, chunk, softcap, block_q,
                      block_k):
    """The jnp forward: returns (o (B, Sq, H, D), lse (B, H, Sq, 1))."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    bq, bk, n_band, banded = _plan(Sq, Sk, window=window, chunk=chunk,
                                   block_q=block_q, block_k=block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)
    qf = q.reshape(B, nq, bq, KV, G, D)
    kb = k.reshape(B, nk, bk, KV, D)
    vb = v.reshape(B, nk, bk, KV, D)

    def q_block(i, _):
        qi = qf[:, i].astype(jnp.float32)
        q_pos = i * bq + jnp.arange(bq)
        base = ((i * bq) // bk - (n_band - 1)) if banded else 0

        def kv_block(inner, __):
            j, m_c, l_c, acc = inner
            kj = jnp.clip(base + j, 0, nk - 1)
            kblk = kb[:, kj].astype(jnp.float32)
            vblk = vb[:, kj].astype(jnp.float32)
            k_pos = kj * bk + jnp.arange(bk)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kblk) * scale
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            m = _block_mask(q_pos, k_pos, (base + j) >= 0,
                            causal=causal, window=window, chunk=chunk)
            s = jnp.where(m[None, None, None], s, NEG_INF)
            m_n = jnp.maximum(m_c, s.max(-1))
            p = jnp.where(m[None, None, None], jnp.exp(s - m_n[..., None]), 0.0)
            corr = jnp.exp(m_c - m_n)
            l_n = l_c * corr + p.sum(-1)
            acc_n = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vblk)
            return (j + 1, m_n, l_n, acc_n), None

        init = (
            jnp.zeros((), jnp.int32),
            jnp.full((B, KV, G, bq), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, G, bq), jnp.float32),
            jnp.zeros((B, KV, G, bq, D), jnp.float32),
        )
        (_, m_f, l_f, acc), _ = jax.lax.scan(
            kv_block, init, None, length=n_band)
        l_safe = jnp.maximum(l_f, 1e-20)
        o = acc / l_safe[..., None]
        o = jnp.moveaxis(o, 3, 1).reshape(B, bq, H, D)
        lse = m_f + jnp.log(l_safe)  # (B, KV, G, bq)
        return i + 1, (o.astype(q.dtype), lse)

    _, (o_blocks, lse_blocks) = jax.lax.scan(
        q_block, jnp.zeros((), jnp.int32), None, length=nq)
    o = jnp.moveaxis(o_blocks, 0, 1).reshape(B, Sq, H, D)
    lse = jnp.moveaxis(lse_blocks, 0, 3).reshape(B, H, Sq, 1)
    return o, lse


def blockwise_backward(q, k, v, o, lse, do, *, causal, window, chunk,
                       softcap, block_q, block_k):
    """dq, dk, dv from the forward's o and lse (B, H, Sq, 1)."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    lse = lse.reshape(B, KV, G, Sq)
    bq, bk, n_band, banded = _plan(Sq, Sk, window=window, chunk=chunk,
                                   block_q=block_q, block_k=block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)

    qf = q.reshape(B, nq, bq, KV, G, D)
    kb = k.reshape(B, nk, bk, KV, D)
    vb = v.reshape(B, nk, bk, KV, D)
    dof = do.reshape(B, nq, bq, KV, G, D)
    # under shard_map the scan carries must vary over q's mesh axes as the
    # blocks do: seed them from q (outside it that would cost XLA its
    # gather reuse, so a plain zero)
    vzero = (q.reshape(-1)[0] * 0).astype(jnp.float32) \
        if jax.typeof(q).vma else jnp.zeros((), jnp.float32)
    # delta = rowsum(do * o): (B, nq, KV, G, bq)
    delta = jnp.einsum("bnqhd,bnqhd->bnqh",
                       do.reshape(B, nq, bq, H, D).astype(jnp.float32),
                       o.reshape(B, nq, bq, H, D).astype(jnp.float32))
    delta = jnp.moveaxis(delta.reshape(B, nq, bq, KV, G), 2, -1)
    lse_b = lse.reshape(B, KV, G, nq, bq)  # (B,KV,G,nq,bq)

    def q_block(carry, _):
        i, dk_acc, dv_acc = carry
        qi = qf[:, i].astype(jnp.float32)
        doi = dof[:, i].astype(jnp.float32)
        q_pos = i * bq + jnp.arange(bq)
        base = ((i * bq) // bk - (n_band - 1)) if banded else 0
        lse_i = lse_b[:, :, :, i]   # (B,KV,G,bq)
        delta_i = delta[:, i]       # (B,KV,G,bq)

        def kv_block(inner, __):
            j, dq_blk, dk_a, dv_a = inner
            kj = jnp.clip(base + j, 0, nk - 1)
            kblk = kb[:, kj].astype(jnp.float32)
            vblk = vb[:, kj].astype(jnp.float32)
            k_pos = kj * bk + jnp.arange(bk)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kblk) * scale
            if softcap > 0:
                sc = jnp.tanh(s / softcap)
                s = sc * softcap
            m = _block_mask(q_pos, k_pos, (base + j) >= 0,
                            causal=causal, window=window, chunk=chunk)
            p = jnp.where(m[None, None, None],
                          jnp.exp(s - lse_i[..., None]), 0.0)
            dv_blk = jnp.einsum("bkgqs,bkgqd->bskd", p, doi.transpose(0, 2, 3, 1, 4))
            dp = jnp.einsum("bkgqd,bskd->bkgqs",
                            doi.transpose(0, 2, 3, 1, 4), vblk)
            ds = p * (dp - delta_i[..., None])
            if softcap > 0:
                ds = ds * (1.0 - jnp.square(sc))
            ds = ds * scale
            dq_blk = dq_blk + jnp.einsum("bkgqs,bskd->bqkgd", ds, kblk)
            dk_blk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qi)
            dk_a = jax.lax.dynamic_update_slice(
                dk_a, jax.lax.dynamic_slice(
                    dk_a, (0, kj * bk, 0, 0), (B, bk, KV, D)) + dk_blk,
                (0, kj * bk, 0, 0))
            dv_a = jax.lax.dynamic_update_slice(
                dv_a, jax.lax.dynamic_slice(
                    dv_a, (0, kj * bk, 0, 0), (B, bk, KV, D)) + dv_blk,
                (0, kj * bk, 0, 0))
            return (j + 1, dq_blk, dk_a, dv_a), None

        init = (jnp.zeros((), jnp.int32),
                jnp.zeros((B, bq, KV, G, D), jnp.float32) + vzero,
                dk_acc, dv_acc)
        (_, dq_blk, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_block, init, None, length=n_band)
        return (i + 1, dk_acc, dv_acc), dq_blk

    init = (jnp.zeros((), jnp.int32),
            jnp.zeros((B, Sk, KV, D), jnp.float32) + vzero,
            jnp.zeros((B, Sk, KV, D), jnp.float32) + vzero)
    (_, dk, dv), dq_blocks = jax.lax.scan(q_block, init, None, length=nq)
    dq = jnp.moveaxis(dq_blocks, 0, 1).reshape(B, Sq, H, D).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
