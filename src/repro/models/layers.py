"""Transformer layer building blocks: norms, RoPE, attention, FFN, MoE.

Every function takes/returns plain arrays; parameters come in as dicts built
from the ParamDef trees in ``repro.models.transformer``.  Activation
shardings are expressed through logical-axis constraints (no-ops outside a
mesh context).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, MoESpec, YaRNSpec
from repro.kernels import ops
from repro.models.params import ParamDef
from repro.parallel.axes import constrain

import os as _os

# bf16 is the production compute dtype; tests that need exactness set
# REPRO_COMPUTE_DTYPE=float32 before importing repro.
COMPUTE_DTYPE = (
    jnp.float32
    if _os.environ.get("REPRO_COMPUTE_DTYPE") == "float32"
    else jnp.bfloat16
)


# ---------------------------------------------------------------------------
# Norms / RoPE
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_frequencies(theta: float, dim: int, yarn: YaRNSpec) -> np.ndarray:
    """The (dim/2,) rotary frequencies YaRN gives a head of ``dim``: each
    original frequency theta^(-2i/dim) blended with its value divided by
    ``factor``, by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_max_position``."""
    half = dim // 2
    extrapolated = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / dim)
    interpolated = extrapolated / yarn.factor

    def dim_of(rotations: float) -> float:
        return dim * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (interpolated * ramp + extrapolated * (1.0 - ramp)).astype(
        np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn: Optional[YaRNSpec] = None) -> jax.Array:
    """x: (..., S, H, D); positions: (S,) or broadcastable.  With ``yarn``
    the frequencies are YaRN's (computed once, at trace time) and cos and
    sin are scaled by its attention factor."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(yarn_frequencies(theta, d, yarn))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (S, half)
    cos = jnp.cos(ang)[..., None, :]  # (S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def attention_defs(cfg: ArchConfig, cross: bool = False) -> dict:
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    defs = {
        "wq": ParamDef((d, H, Dh), ("embed", "q_heads", "head_dim")),
        "wk": ParamDef((d, KV, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, KV, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, Dh, d), ("q_heads", "head_dim", "embed")),
    }
    if cfg.qk_norm and not cross:
        defs["q_norm"] = ParamDef((Dh,), ("head_dim",), init="ones")
        defs["k_norm"] = ParamDef((Dh,), ("head_dim",), init="ones")
    return defs


def _project_qkv(p: dict, xq: jax.Array, xkv: jax.Array, cfg: ArchConfig,
                 positions: Optional[jax.Array], kind: Optional[str]):
    """q, k, v; rotated for a self-attention layer of ``kind`` (None: cross
    attention, unrotated)."""
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"].astype(xq.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"].astype(xkv.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"].astype(xkv.dtype))
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if kind is not None and positions is not None:
        yarn = cfg.yarn if kind == "global" else None
        q = rope(q, positions, cfg.rope_theta, yarn)
        k = rope(k, positions, cfg.rope_theta, yarn)
    q = constrain(q, "act_batch", "act_seq", "act_heads", None)
    k = constrain(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = constrain(v, "act_batch", "act_seq", "act_kv_heads", None)
    return q, k, v


def self_attention(
    p: dict,
    x: jax.Array,  # (B, S, d) pre-normed input
    cfg: ArchConfig,
    kind: str,  # global | local | chunked
    *,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns (attn output, (k, v)) — k/v reused for prefill cache writes."""
    q, k, v = _project_qkv(p, x, x, cfg, positions, kind)
    window = cfg.window if kind == "local" else 0
    chunk = cfg.window if kind == "chunked" else 0
    o = ops.flash_attention(
        q, k, v, causal=causal, window=window, chunk=chunk,
        softcap=cfg.attn_logit_softcap,
    )
    o = constrain(o, "act_batch", "act_seq", "act_heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    # Constraining this output to the sequence-parallel layout, hoping for
    # a reduce-scatter lowering, cost granite's sharded step 10% (a CPU
    # roofline estimate from compiled HLO, not a chip time) and broke the
    # MoE dispatch path: outputs stay seq-replicated and the boundary
    # constraint in run_groups does the SP transition.
    out = constrain(out, "act_batch", "act_seq", None)
    if cfg.remat_policy == "save_attn":
        # the inert name primitive blocks gather-reuse fusions (10% more
        # all-gather in granite's sharded HLO): only tag when the policy
        # uses it
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "attn_out")
    return out, (k, v)


def cross_attention(
    p: dict,
    x: jax.Array,        # (B, S, d) pre-normed decoder stream
    enc_out: jax.Array,  # (B, Se, d) encoder output
    cfg: ArchConfig,
) -> jax.Array:
    q, k, v = _project_qkv(p, x, enc_out, cfg, None, None)
    o = ops.flash_attention(q, k, v, causal=False)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return constrain(out, "act_batch", "act_seq", None)


def decode_self_attention(
    p: dict,
    x: jax.Array,  # (B, 1, d)
    cfg: ArchConfig,
    kind: str,
    k_cache: jax.Array,  # (layers, L, KV, B, Dh): every repeat's
    v_cache: jax.Array,
    pos: jax.Array,  # scalar int32 current position
    layer: jax.Array,  # scalar int32: this layer's index in the stack
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention; returns (out, new_k_cache, new_v_cache), the
    stacks with this layer's slot for ``pos`` written in place."""
    B, _, _ = x.shape
    L = k_cache.shape[1]
    positions = pos[None]  # (1,)
    q, k, v = _project_qkv(p, x, x, cfg, positions, kind)
    slot = pos % L  # ring slot (== pos for a full-length global cache)
    at = (layer, slot, 0, 0, 0)
    # (B, 1, KV, Dh) -> (1, 1, KV, B, Dh)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.transpose(1, 2, 0, 3)[None].astype(k_cache.dtype), at)
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.transpose(1, 2, 0, 3)[None].astype(v_cache.dtype), at)
    # absolute position stored in each slot of a ring buffer
    idx = jnp.arange(L)
    if kind == "global":
        slot_pos = jnp.where(idx <= pos, idx, -1)
    else:
        cand = pos - ((pos - idx) % L)
        slot_pos = jnp.where(cand >= 0, cand, -1)
    slot_pos = jnp.broadcast_to(slot_pos[None], (B, L))
    window = cfg.window if kind == "local" else 0
    chunk = cfg.window if kind == "chunked" else 0
    o = ops.decode_attention(
        q, k_cache[layer], v_cache[layer], slot_pos,
        jnp.broadcast_to(pos[None], (B,)),
        window=window, chunk=chunk, softcap=cfg.attn_logit_softcap,
    )
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# Dense FFN (SwiGLU)
# ---------------------------------------------------------------------------
def ffn_defs(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    defs = {
        "w_up": ParamDef((d, f), ("embed", "ff")),
        "w_down": ParamDef((f, d), ("ff", "embed")),
    }
    if cfg.ffn_gated:
        defs["w_gate"] = ParamDef((d, f), ("embed", "ff"))
    return defs


def ffn(p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if "w_gate" in p:  # SwiGLU
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = jax.nn.silu(g) * u
    else:  # classic MLP
        h = jax.nn.gelu(u)
    h = constrain(h, "act_batch", "act_seq", "act_ff")
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    return constrain(out, "act_batch", "act_seq", None)


# ---------------------------------------------------------------------------
# Mixture of Experts: dropless (routes sorted by expert, grouped matmuls) or
# t5x-style dispatch/combine with per-group capacity
# ---------------------------------------------------------------------------
def moe_defs(cfg: ArchConfig) -> dict:
    assert cfg.moe is not None
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    defs = {
        "router": ParamDef((d, E), ("embed", "experts"), init_scale=0.1),
        "w_gate": ParamDef((E, d, f), ("experts", "embed", "ff")),
        "w_up": ParamDef((E, d, f), ("experts", "embed", "ff")),
        "w_down": ParamDef((E, f, d), ("experts", "ff", "embed")),
    }
    if cfg.moe.shared_expert:
        defs["shared"] = ffn_defs(cfg)
    return defs


def _capacity(spec: MoESpec, group: int) -> int:
    c = int(np.ceil(group * spec.top_k * spec.capacity_factor / spec.n_experts))
    return max(4, int(np.ceil(c / 4)) * 4)


def _router(p: dict, x: jax.Array, top_k: int):
    """float32 router logits and softmax, and each token's top-k experts
    with their probabilities renormalised to sum to 1."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, gate_idx


def _aux_losses(spec: MoESpec, logits, probs, routed_frac) -> dict:
    """Switch-style load balance and router z-loss: the train loss's
    terms (a prefill or decode step leaves them to dead-code removal)."""
    E = spec.n_experts
    lb = (probs.mean(axis=-2) * routed_frac).sum(-1).mean() * E \
        * spec.load_balance_loss
    z = jax.nn.logsumexp(logits, axis=-1)
    return {"moe_lb_loss": lb, "moe_z_loss": (z**2).mean() * spec.router_z_loss}


def moe_ffn(p: dict, x: jax.Array, cfg: ArchConfig, experts=None,
            layer=None) -> tuple[jax.Array, dict]:
    """Routed expert FFN.  Returns (output, aux): the aux losses, the share
    of routes dropped, and ``routed``, the (E,) int32 count of the routes
    each expert received.

    A dropless layer may take its expert weights apart from ``p``:
    ``experts`` holds every repeat's, stacked (R, E, ...), and ``layer``
    is this one's repeat index."""
    spec = cfg.moe
    assert spec is not None
    if spec.dropless:
        with jax.named_scope("moe"):
            return _moe_dropless(p, x, cfg, experts, layer)
    B, S, d = x.shape
    E, K = spec.n_experts, spec.top_k
    T = B * S
    G = min(spec.group_size, T)
    while T % G:  # largest divisor of T not exceeding group_size
        G -= 1
    n_groups = T // G
    C = _capacity(spec, G)
    dt = x.dtype

    # unshard the sequence before grouping (the residual stream is
    # sequence-parallel; dispatch must see whole groups)
    x = constrain(x, "act_batch", "act_seq", None)
    xg = x.reshape(n_groups, G, d)
    # groups inherit the token sharding: g = (batch x seq-chunks)
    xg = constrain(xg, "act_batch", None, None)
    logits, probs, gate_vals, gate_idx = _router(p, xg, K)  # (g, G, K)

    # expert one-hot per routing slot: (g, G, K, E)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    # position of each token within its expert queue (capacity enforcement)
    pos_in_expert = jnp.cumsum(onehot.reshape(n_groups, G * K, E), axis=1)
    pos_in_expert = (pos_in_expert - 1).reshape(n_groups, G, K, E)
    keep = (pos_in_expert < C) & (onehot > 0)
    cap_slot = jnp.where(keep, pos_in_expert, 0).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(cap_slot, C, dtype=jnp.float32) * keep[..., None]
    # dispatch: (g, G, E, C); combine adds the gate weights
    dispatch = (onehot[..., None] * slot_oh).sum(2)
    combine = (gate_vals[..., None, None] * onehot[..., None] * slot_oh).sum(2)

    # dispatch/combine run in compute dtype: the dispatch matmul is an exact
    # permutation (one-hot), and combine's bf16 gates match standard practice
    dispatch = constrain(dispatch.astype(dt), "act_batch", None, "act_experts", None)
    combine = constrain(combine.astype(dt), "act_batch", None, "act_experts", None)
    xin = jnp.einsum("gtd,gtec->gecd", xg, dispatch)
    xin = constrain(xin, "act_batch", "act_experts", None, None)
    g_ = jnp.einsum("gecd,edf->gecf", xin, p["w_gate"].astype(dt))
    u_ = jnp.einsum("gecd,edf->gecf", xin, p["w_up"].astype(dt))
    h = jax.nn.silu(g_) * u_
    h = constrain(h, "act_batch", "act_experts", None, "act_ff")
    eo = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
    # no constraint on eo: its TP partial-sum may be deferred through the
    # (linear) combine einsum, reducing (g,G,d) instead of (g,E,C,d)
    out = jnp.einsum("gecd,gtec->gtd", eo, combine)
    out = out.reshape(B, S, d)
    out = constrain(out, "act_batch", "act_seq", None)

    if "shared" in p:
        out = out + ffn(p["shared"], x)

    aux = _aux_losses(spec, logits, probs, onehot.sum(2).mean(axis=1))
    aux["moe_dropped_frac"] = 1.0 - (keep.sum() / (n_groups * G * K))
    aux["routed"] = onehot.sum((0, 1, 2)).astype(jnp.int32)
    return out, aux


def _moe_dropless(p: dict, x: jax.Array, cfg: ArchConfig, experts, layer):
    """Every token through its top-k experts, none dropped: the T*K routes
    sorted by expert, each expert's contiguous rows through its weights in
    a grouped matmul (an expert that received no row is not read), then
    unsorted and summed under the gate weights.

    The expert weights are ``p``'s (E, ...), or ``experts``, every
    repeat's stacked (R, E, ...): the grouped matmul then runs over all
    R * E of them, with rows in the E groups of repeat ``layer`` only."""
    spec = cfg.moe
    B, S, d = x.shape
    E, K = spec.n_experts, spec.top_k
    T = B * S
    dt = x.dtype
    xt = x.reshape(T, d)
    logits, probs, gate_vals, gate_idx = _router(p, xt, K)  # (T, K)
    expert = gate_idx.reshape(T * K)
    order = jnp.argsort(expert, stable=True)      # route slots by expert
    sizes = jnp.bincount(expert, length=E).astype(jnp.int32)
    rows = jnp.take(xt, order // K, axis=0)       # (T*K, d), sorted
    groups = sizes
    if experts is None:
        w = [p[k].astype(dt) for k in ("w_gate", "w_up", "w_down")]
    else:
        w = [experts[k].astype(dt) for k in ("w_gate", "w_up", "w_down")]
        R = w[0].shape[0]
        w = [a.reshape(R * E, *a.shape[2:]) for a in w]
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((R * E,), jnp.int32), sizes, (layer * E,))
    g = ops.grouped_matmul(rows, w[0], groups)
    u = ops.grouped_matmul(rows, w[1], groups)
    y = ops.grouped_matmul(jax.nn.silu(g) * u, w[2], groups)
    # route slot r's output is sorted row where[r]
    where = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32), unique_indices=True)
    y = jnp.take(y, where, axis=0).reshape(T, K, d).astype(jnp.float32)
    out = (y * gate_vals[..., None]).sum(1).astype(dt).reshape(B, S, d)
    if "shared" in p:
        out = out + ffn(p["shared"], x)
    aux = _aux_losses(spec, logits, probs, sizes.astype(jnp.float32) / T)
    aux["moe_dropped_frac"] = jnp.zeros((), jnp.float32)
    aux["routed"] = sizes
    return out, aux
