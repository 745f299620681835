"""jit-ready compute ops used by the model zoo.

Each op dispatches between
  * a Pallas TPU kernel (``repro.kernels.<name>``) when running on TPU, and
  * a memory-bounded blockwise jnp implementation (lowered for the CPU
    dry-run and executed in smoke tests).

The jnp paths are written flash-style (online softmax over KV blocks, banded
gathering for local/chunked attention) so the *lowered HLO* — which is what
the roofline analysis reads — never materializes an S x S score matrix and
carries near-optimal FLOPs for windowed attention.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref

NEG_INF = -1e30


def use_pallas() -> bool:
    """The Pallas kernels run wherever the platform is a TPU, and only
    there (other platforms take the jnp paths)."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Flash attention (training / prefill)
#
# The jnp path carries an explicit flash-style custom VJP: the backward pass
# recomputes block probabilities from (q, k, lse) instead of letting jax
# save every per-block residual of the forward scan (which would silently
# re-materialize the S x S attention matrix in HBM).  Block indices are
# carried as dynamic counters — not scan xs — so XLA cannot hoist the
# causal/window masks into giant loop-invariant buffers.
# ---------------------------------------------------------------------------
def _pick_block(s: int, target: int) -> int:
    b = min(s, target)
    while s % b:
        b //= 2
    return max(b, 1)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,   # sliding-window width (0 = unbounded)
    chunk: int = 0,    # chunked-attention width (0 = off)
    softcap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 0,
    block_k: int = 0,
) -> jax.Array:
    # hillclimb knobs: block sizes tune the VMEM working set / HLO traffic
    block_q = block_q or int(os.environ.get("REPRO_FLASH_BLOCK_Q", 1024))
    block_k = block_k or int(os.environ.get("REPRO_FLASH_BLOCK_K", 1024))
    if use_pallas() and q.shape[1] == k.shape[1] and q_offset == 0:
        return pallas_flash(q, k, v, causal=causal, window=window,
                            chunk=chunk, softcap=softcap)
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    if Sq * Sk <= 1024 * 1024:  # tiny: the oracle is cheaper than blocking
        return kref.attention_ref(
            q, k, v, causal=causal, window=window, chunk=chunk,
            softcap=softcap, q_offset=q_offset,
        )
    cp = _maybe_context_parallel(q, k, v, causal=causal, window=window,
                                 chunk=chunk, softcap=softcap,
                                 q_offset=q_offset, block_q=block_q,
                                 block_k=block_k)
    if cp is not None:
        return cp
    return _flash(q, k, v, causal, window, chunk, softcap, q_offset,
                  block_q, block_k)


def pallas_flash(q, k, v, *, interpret: bool = False, **kw) -> jax.Array:
    """The Pallas flash kernel, under ``shard_map`` when a mesh context is
    active: XLA cannot partition a ``pallas_call``, so each device runs the
    kernel on its own batch shard, and on its own head shard when both the
    q and the kv head counts divide the model axis (a kv head then stays
    with its query group).  ``interpret`` is for tests."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import flash_attention as fak
    from repro.parallel import axes as paxes

    call = functools.partial(fak.flash_attention, interpret=interpret, **kw)
    mesh = paxes._CTX.mesh
    if mesh is None:
        return call(q, k, v)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_batch = int(np.prod([mesh.shape[a] for a in batch_axes]))
    bspec = batch_axes if batch_axes and q.shape[0] % n_batch == 0 else None
    n_model = mesh.shape.get("model", 1)
    heads = n_model > 1 and q.shape[2] % n_model == 0 \
        and k.shape[2] % n_model == 0
    spec = P(bspec, None, "model" if heads else None, None)
    return jax.shard_map(call, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def _maybe_context_parallel(q, k, v, *, causal, window, chunk, softcap,
                            q_offset, block_q, block_k):
    """Context-parallel flash attention over the TP axis.

    When an architecture's head count does not divide the model axis (e.g.
    24 heads on a 16-way axis, or MQA), plain SPMD *replicates* the whole
    attention computation on every model-axis device — 16x the FLOPs and
    score traffic.  Here we shard the q sequence over the model axis with
    shard_map instead: each device computes attention for its S/n query
    rows against the (small, replicated) K/V, with causal masks offset by
    the shard's global position.  dK/dV cotangents psum automatically via
    shard_map's replicated-input transpose.
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel import axes as paxes

    mesh = paxes._CTX.mesh
    if mesh is None or "model" not in mesh.shape:
        return None
    n = mesh.shape["model"]
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    if H % n == 0:  # heads shard fine: standard TP attention is better
        return None
    if window or chunk or Sq != Sk or q_offset != 0 or Sq % n != 0:
        return None
    s_local = Sq // n
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = batch_axes[0] if len(batch_axes) == 1 else (batch_axes or None)
    q_spec = P(bspec, "model", None, None)
    kv_spec = P(bspec, None, None, None)

    def inner(qs, ks, vs):
        idx = jax.lax.axis_index("model")
        off = (idx * s_local).astype(jnp.float32)
        return _flash_off(qs, ks, vs, off, causal, softcap,
                          min(block_q, s_local), block_k)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_off(q, k, v, q_offset_f, causal, softcap, block_q, block_k):
    o, _ = _flash_fwd_impl(q, k, v, causal, 0, 0, softcap,
                           q_offset_f.astype(jnp.int32), block_q, block_k,
                           seed_carries=True)
    return o


def _flash_off_fwd(q, k, v, q_offset_f, causal, softcap, block_q, block_k):
    off = q_offset_f.astype(jnp.int32)
    o, lse = _flash_fwd_impl(q, k, v, causal, 0, 0, softcap, off,
                             block_q, block_k, seed_carries=True)
    return o, (q, k, v, o, lse, q_offset_f)


def _flash_off_bwd(causal, softcap, block_q, block_k, res, do):
    q, k, v, o, lse, q_offset_f = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, o, lse, do, causal=causal, window=0, chunk=0,
        softcap=softcap, q_offset=q_offset_f.astype(jnp.int32),
        block_q=block_q, block_k=block_k, seed_carries=True)
    # K/V are replicated across the context-parallel axis: their cotangent
    # is the sum of every q-shard's contribution
    dk = jax.lax.psum(dk, "model")
    dv = jax.lax.psum(dv, "model")
    return dq, dk, dv, jnp.zeros_like(q_offset_f)


_flash_off.defvjp(_flash_off_fwd, _flash_off_bwd)


def _plan(Sq, Sk, *, causal, window, chunk, q_offset, block_q, block_k):
    """Blocking plan: block sizes + per-q-block kv band."""
    band = window if window > 0 else chunk
    static_zero_offset = isinstance(q_offset, int) and q_offset == 0
    if band > 0 and Sq == Sk and Sq >= band and Sq % band == 0 \
            and static_zero_offset:
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, chunk, softcap, q_offset, block_q, block_k):
    o, _ = _flash_fwd_impl(q, k, v, causal, window, chunk, softcap, q_offset,
                           block_q, block_k)
    return o


def _flash_fwd_impl(q, k, v, causal, window, chunk, softcap, q_offset,
                    block_q, block_k, seed_carries=False):
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    bq, bk, n_band, banded = _plan(
        Sq, Sk, causal=causal, window=window, chunk=chunk, q_offset=q_offset,
        block_q=block_q, block_k=block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)
    qf = q.reshape(B, nq, bq, KV, G, D)
    kb = k.reshape(B, nk, bk, KV, D)
    vb = v.reshape(B, nk, bk, KV, D)
    # input-derived zero: keeps scan-carry vma types consistent under
    # shard_map (context-parallel path only — outside shard_map it blocks
    # XLA's gather-reuse and costs ~10% extra all-gather, see §Perf)
    vzero = (q.reshape(-1)[0] * 0).astype(jnp.float32) if seed_carries \
        else jnp.zeros((), jnp.float32)

    def q_block(i, _):
        qi = qf[:, i].astype(jnp.float32)
        q_pos = q_offset + i * bq + jnp.arange(bq)
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
            jnp.full((B, KV, G, bq), NEG_INF, jnp.float32) + vzero,
            jnp.zeros((B, KV, G, bq), jnp.float32) + vzero,
            jnp.zeros((B, KV, G, bq, D), jnp.float32) + vzero,
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
    lse = jnp.moveaxis(lse_blocks, 0, 3).reshape(B, KV, G, Sq)  # (nq-major, bq)
    return o, lse


def _flash_fwd(q, k, v, causal, window, chunk, softcap, q_offset,
               block_q, block_k):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, chunk, softcap,
                             q_offset, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, chunk, softcap, q_offset, block_q, block_k,
               res, do):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, o, lse, do, causal=causal, window=window,
                           chunk=chunk, softcap=softcap, q_offset=q_offset,
                           block_q=block_q, block_k=block_k)


def _flash_bwd_impl(q, k, v, o, lse, do, *, causal, window, chunk, softcap,
                    q_offset, block_q, block_k, seed_carries=False):
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    bq, bk, n_band, banded = _plan(
        Sq, Sk, causal=causal, window=window, chunk=chunk, q_offset=q_offset,
        block_q=block_q, block_k=block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)

    qf = q.reshape(B, nq, bq, KV, G, D)
    kb = k.reshape(B, nk, bk, KV, D)
    vb = v.reshape(B, nk, bk, KV, D)
    dof = do.reshape(B, nq, bq, KV, G, D)
    vzero = (q.reshape(-1)[0] * 0).astype(jnp.float32) if seed_carries \
        else jnp.zeros((), jnp.float32)
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
        q_pos = q_offset + i * bq + jnp.arange(bq)
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


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Decode attention (one new token against a cache)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jax.Array,         # (B, 1, H, D)
    k_cache: jax.Array,   # (B, L, KV, D)
    v_cache: jax.Array,
    slot_pos: jax.Array,  # (B, L)
    pos: jax.Array,       # (B,)
    *,
    window: int = 0,
    chunk: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    return kref.decode_attention_ref(
        q, k_cache, v_cache, slot_pos, pos,
        window=window, chunk=chunk, softcap=softcap,
    )


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) WKV recurrence
# ---------------------------------------------------------------------------
def wkv6(
    r: jax.Array,  # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # per-step decay in (0,1)
    u: jax.Array,  # (H, D)
    state: Optional[jax.Array] = None,  # (B, H, D, D)
) -> tuple[jax.Array, jax.Array]:
    if use_pallas():
        from repro.kernels import rwkv6_scan as k6

        return k6.wkv6(r, k, v, w, u, state)
    return kref.wkv6_ref(r, k, v, w, u, state)


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence (parallel associative scan)
# ---------------------------------------------------------------------------
def rglru(
    x: jax.Array,      # (B, S, W) gated input
    log_a: jax.Array,  # (B, S, W) log recurrence coefficient (<= 0)
    h0: Optional[jax.Array] = None,  # (B, W)
) -> tuple[jax.Array, jax.Array]:
    if use_pallas():
        from repro.kernels import rglru_scan as kg

        return kg.rglru(x, log_a, h0)
    xf = x.astype(jnp.float32)
    laf = log_a.astype(jnp.float32)
    a = jnp.exp(laf)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * laf), 1e-12)) * xf

    def comb(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    ca, hb = jax.lax.associative_scan(comb, (a, b), axis=1)
    if h0 is not None:
        hb = hb + ca * h0[:, None, :].astype(jnp.float32)
    return hb.astype(x.dtype), hb[:, -1].astype(jnp.float32)


def causal_conv1d(
    x: jax.Array,  # (B, S, W)
    w: jax.Array,  # (K, W) depthwise taps, w[-1] multiplies x_t
    state: Optional[jax.Array] = None,  # (B, K-1, W) trailing context
) -> tuple[jax.Array, jax.Array]:
    B, S, W = x.shape
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((B, K - 1, W), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, S+K-1, W)
    out = jnp.zeros_like(x)
    for i in range(K):
        out = out + xp[:, i : i + S] * w[i]
    new_state = xp[:, S:]  # last K-1 inputs
    return out, new_state
