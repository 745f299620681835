"""Pure-jnp reference oracles for every kernel in this package.

These are the ground truth for the Pallas kernels' allclose tests and are
also used directly for tiny shapes.  They intentionally favour clarity over
memory efficiency (naive attention materializes the full score matrix).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _mask(q_pos, k_pos, *, causal: bool, window: int, chunk: int):
    """(Sq, Sk) boolean mask. True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    if chunk > 0:
        m &= (q_pos[:, None] // chunk) == (k_pos[None, :] // chunk)
    return m


def attention_ref(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,  # (B, Sk, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
) -> jax.Array:
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) / np.sqrt(D)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    m = _mask(q_pos, k_pos, causal=causal, window=window, chunk=chunk)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key (shouldn't happen for causal) -> zero out
    p = jnp.where(m.any(-1)[None, None, None, :, None], p, 0.0)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,        # (B, 1, H, D)
    k_cache: jax.Array,  # (L, KV, B, D)
    v_cache: jax.Array,  # (L, KV, B, D)
    slot_pos: jax.Array,  # (B, L) absolute position per slot, -1 = empty
    pos: jax.Array,      # (B,) current query position
    *,
    window: int = 0,
    chunk: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    B, _, H, D = q.shape
    L, KV, _, _ = k_cache.shape
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,lkbd->bkgl", qf, k_cache.astype(jnp.float32)) / np.sqrt(D)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window > 0:
        valid &= (pos[:, None] - slot_pos) < window
    if chunk > 0:
        valid &= (slot_pos // chunk) == (pos[:, None] // chunk)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid.any(-1)[:, None, None, None], p, 0.0)
    o = jnp.einsum("bkgl,lkbd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


def wkv6_ref(
    r: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, H, D)
    v: jax.Array,  # (B, S, H, D)
    w: jax.Array,  # (B, S, H, D) per-step decay in (0, 1)
    u: jax.Array,  # (H, D) bonus for the current token
    state: jax.Array | None = None,  # (B, H, D, D) [key-dim x value-dim]
) -> tuple[jax.Array, jax.Array]:
    """RWKV-6 (Finch) recurrence, exact sequential form.

    out_t = r_t . (S_t + diag(u) k_t^T v_t);  S_{t+1} = diag(w_t) S_t + k_t^T v_t
    """
    B, S, H, D = r.shape
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)

    def step(S_c, inp):
        r_t, k_t, v_t, w_t = inp  # (B, H, D)
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B,H,D,D)
        out = jnp.einsum("bhd,bhde->bhe", r_t, S_c + uf[None, :, :, None] * kv)
        S_n = w_t[..., :, None] * S_c + kv
        return S_n, out

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (rf, kf, vf, wf))
    state_f, outs = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(outs, 0, 1).astype(r.dtype), state_f


def rglru_ref(
    x: jax.Array,      # (B, S, W) gated input (i_t * x_t)
    log_a: jax.Array,  # (B, S, W) log recurrence coefficient, <= 0
    h0: jax.Array | None = None,  # (B, W)
) -> tuple[jax.Array, jax.Array]:
    """RG-LRU linear recurrence, exact sequential form.

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t
    """
    B, S, W = x.shape
    xf = x.astype(jnp.float32)
    laf = log_a.astype(jnp.float32)
    a = jnp.exp(laf)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * laf), 1e-12)) * xf
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)

    def step(h, inp):
        a_t, b_t = inp
        h_n = a_t * h + b_t
        return h_n, h_n

    h_f, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                           (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), h_f
