"""jit-ready compute ops used by the model zoo.

Each op dispatches between
  * a Pallas TPU kernel (``repro.kernels.<name>``) when running on TPU, and
  * a jnp implementation on other platforms: the blockwise flash paths,
    which never materialize an S x S score matrix, the installed JAX's
    ``ragged_dot``, or the pure-jnp oracles of ``ref.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as fak
from repro.kernels import ref as kref


def use_pallas() -> bool:
    """The Pallas kernels run wherever the platform is a TPU, and only
    there (other platforms take the jnp paths)."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Flash attention (training / prefill): kernels/flash_attention.py
# ---------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,   # sliding-window width (0 = unbounded)
    chunk: int = 0,    # chunked-attention width (0 = off)
    softcap: float = 0.0,
) -> jax.Array:
    """On TPU with Sq == Sk the Pallas forward; otherwise the oracle where
    the scores are small, else the blockwise-jnp forward.  Both flash
    forwards share the blockwise backward."""
    kw = dict(causal=causal, window=window, chunk=chunk, softcap=softcap)
    if use_pallas() and q.shape[1] == k.shape[1]:
        return pallas_flash(q, k, v, **kw)
    if q.shape[1] * k.shape[1] <= 1024 * 1024:  # the oracle is cheaper
        return kref.attention_ref(q, k, v, **kw)
    return fak.blockwise_attention(q, k, v, **kw)


def pallas_flash(q, k, v, *, interpret: bool = False, **kw) -> jax.Array:
    """The Pallas flash kernel, under ``shard_map`` when a mesh context is
    active: XLA cannot partition a ``pallas_call``, so each device runs the
    kernel on its own batch shard, and on its own head shard when both the
    q and the kv head counts divide the model axis (a kv head then stays
    with its query group).  ``interpret`` is for tests."""
    from jax.sharding import PartitionSpec as P

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


# ---------------------------------------------------------------------------
# Decode attention (one new token against a cache)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jax.Array,         # (B, 1, H, D)
    k_cache: jax.Array,   # (L, KV, B, D)
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
# Grouped matmul (the dropless expert layer)
# ---------------------------------------------------------------------------
def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """x (m, k) rows sorted by group, w (g, k, n), group_sizes (g,) int32
    summing to m: row r of group i times w[i], (m, n) in x's dtype with
    float32 accumulation.  A group with no rows reads none of its weights."""
    if use_pallas():
        return pallas_gmm(x, w, group_sizes)
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32).astype(
                                  x.dtype)


def _gmm_tile(dim: int, cap: int = 1152) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``, else ``dim`` whole (a block as wide as its array is legal)."""
    for t in range(min(cap, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def pallas_gmm(x, w, group_sizes, *, interpret: bool = False) -> jax.Array:
    """The Pallas grouped matmul of the installed JAX (megablox ``gmm``).
    Row tiles of 512 (fewer rows: one tile of them, padded to 16, the bf16
    sublane tile); k and n tiles that divide them, at most 1152 wide.
    Padding rows belong to no group and are cut off again."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, k = x.shape
    n = w.shape[2]
    tm = 512 if m >= 512 else -(-m // 16) * 16
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = megablox.gmm(x, w, group_sizes, x.dtype,
                       (tm, _gmm_tile(k), _gmm_tile(n)), None, None, False,
                       interpret)
    return out[:m]


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
