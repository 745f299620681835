"""Model assembly: config -> param defs -> forward / prefill / decode.

Layers are executed as ``lax.scan`` over *block groups* (see configs.base)
so lowered HLO size is independent of depth.  The same layer code serves
training (full sequence), prefill (full sequence + cache write) and decode
(one token + cache update), which keeps the three step functions
(``models.steps``) consistent by construction.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN_KINDS, ArchConfig
from repro.kernels import ops
from repro.models import recurrent
from repro.models.layers import (
    COMPUTE_DTYPE,
    attention_defs,
    cross_attention,
    decode_self_attention,
    ffn,
    ffn_defs,
    moe_defs,
    moe_ffn,
    rms_norm,
    self_attention,
)
from repro.models.params import ParamDef
from repro.parallel.axes import constrain

AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_dropped_frac")


def _aux_zeros() -> jax.Array:
    return jnp.zeros((len(AUX_KEYS),), jnp.float32)


def _aux_vec(d: dict) -> jax.Array:
    return jnp.stack([jnp.asarray(d[k], jnp.float32) for k in AUX_KEYS])


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------
def layer_defs(cfg: ArchConfig, kind: str, with_cross: bool = False) -> dict:
    if kind == "rwkv":
        return recurrent.rwkv_defs(cfg)
    if kind == "rglru":
        return recurrent.rglru_defs(cfg)
    assert kind in ATTN_KINDS
    d = cfg.d_model
    defs: dict[str, Any] = {
        "ln1": ParamDef((d,), ("embed",), init="ones"),
        "attn": attention_defs(cfg),
        "ln2": ParamDef((d,), ("embed",), init="ones"),
    }
    if cfg.moe is not None:
        defs["moe"] = moe_defs(cfg)
    else:
        defs["ffn"] = ffn_defs(cfg)
    if with_cross:
        defs["ln_x"] = ParamDef((d,), ("embed",), init="ones")
        defs["xattn"] = attention_defs(cfg, cross=True)
    return defs


def _stack(defs, n: int):
    return jax.tree_util.tree_map(
        lambda pd: ParamDef(
            (n,) + pd.shape, ("layers",) + pd.axes, pd.dtype, pd.init,
            pd.init_scale, pd.init_fn,
        ),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def model_defs(cfg: ArchConfig) -> dict:
    d, V = cfg.d_model, cfg.vocab_size
    groups = []
    for pattern, repeats in cfg.block_groups:
        g = {
            f"p{i}": _stack(layer_defs(cfg, kind, with_cross=cfg.enc_dec), repeats)
            for i, kind in enumerate(pattern)
        }
        groups.append(g)
    defs: dict[str, Any] = {
        "embed": ParamDef((V, d), ("vocab", "embed")),
        "groups": groups,
        "ln_f": ParamDef((d,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, V), ("embed", "vocab"))
    if cfg.enc_dec:
        defs["encoder"] = {
            "blocks": _stack(layer_defs(cfg, "global"), cfg.n_enc_layers),
            "ln_f": ParamDef((d,), ("embed",), init="ones"),
        }
    return defs


# ---------------------------------------------------------------------------
# Layer application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------
def _apply_attn_layer(cfg, kind, p, h, *, causal, positions, enc_out,
                      experts=None, layer=None):
    a_out, kv = self_attention(
        p["attn"], rms_norm(h, p["ln1"], cfg.norm_eps), cfg, kind,
        causal=causal, positions=positions,
    )
    h = h + a_out
    if enc_out is not None:
        h = h + cross_attention(
            p["xattn"], rms_norm(h, p["ln_x"], cfg.norm_eps), enc_out, cfg)
    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        f_out, aux = moe_ffn(p["moe"], hn, cfg, experts=experts, layer=layer)
    else:
        f_out, aux = ffn(p["ffn"], hn), None
    return h + f_out, aux, kv


def apply_layer(cfg, kind, p, h, *, causal=True, positions=None, enc_out=None,
                experts=None, layer=None):
    """Full-sequence layer application. Returns (h, aux, prefill_cache).
    ``experts`` and ``layer`` go to ``moe_ffn`` (see ``_layer_scan``)."""
    if kind == "rwkv":
        h, state = recurrent.rwkv_block(p, h, cfg)
        return h, _aux_zeros(), state
    if kind == "rglru":
        h, state = recurrent.rglru_block(p, h, cfg)
        return h, _aux_zeros(), state
    h, aux, (k, v) = _apply_attn_layer(
        cfg, kind, p, h, causal=causal, positions=positions, enc_out=enc_out,
        experts=experts, layer=layer)
    S = k.shape[1]
    L = cfg.kv_cache_len(kind, S)
    k, v = k[:, -L:], v[:, -L:]
    if S % L:  # a ring holds position p in slot p % L, where decode looks
        k, v = jnp.roll(k, S % L, axis=1), jnp.roll(v, S % L, axis=1)
    # stored (L, KV, B, D), the order decode's attention reads
    cache = {n: x.transpose(1, 2, 0, 3).astype(COMPUTE_DTYPE)
             for n, x in (("k", k), ("v", v))}
    if aux is None:
        aux_vec = _aux_zeros()
    else:
        aux_vec = _aux_vec(aux)
        cache.update(routed=aux["routed"], touched=jnp.zeros((), jnp.int32))
    if enc_out is not None:
        # cache cross-attention K/V for decode
        xp = p["xattn"]
        xk = jnp.einsum("bsd,dhk->bshk", enc_out, xp["wk"].astype(enc_out.dtype))
        xv = jnp.einsum("bsd,dhk->bshk", enc_out, xp["wv"].astype(enc_out.dtype))
        cache["xk"] = xk.astype(COMPUTE_DTYPE)
        cache["xv"] = xv.astype(COMPUTE_DTYPE)
    return h, aux_vec, cache


def _decode_cross_attention(p, x, xk, xv, cfg):
    B = x.shape[0]
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    KV = xk.shape[2]
    H = q.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, cfg.d_head)
    s = jnp.einsum("bkgd,blkd->bkgl", qf, xk.astype(jnp.float32))
    s = s / np.sqrt(cfg.d_head)
    pmax = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,blkd->bkgd", pmax, xv.astype(jnp.float32))
    o = o.reshape(B, 1, H, cfg.d_head).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))


def decode_apply_layer(cfg, kind, p, h, cache, pos, layer, experts=None):
    """One-token layer application against a group's stacked cache entry
    ``cache`` (every repeat's, leading axis ``layers``), of which this is
    repeat ``layer``.  Returns (h, cache): the entry with this repeat's
    K/V slot, recurrent state or counters written in place, and the rest
    as it came.  ``experts`` goes to ``moe_ffn`` (see ``_layer_scan``)."""
    if kind in ("rwkv", "rglru"):
        block = {"rwkv": recurrent.rwkv_block,
                 "rglru": recurrent.rglru_block}[kind]
        h, state = block(p, h, cfg,
                         state={k: x[layer] for k, x in cache.items()})
        return h, {k: x.at[layer].set(state[k].astype(x.dtype))
                   for k, x in cache.items()}
    a_out, k_c, v_c = decode_self_attention(
        p["attn"], rms_norm(h, p["ln1"], cfg.norm_eps), cfg, kind,
        cache["k"], cache["v"], pos, layer,
    )
    h = h + a_out
    cache = dict(cache, k=k_c, v=v_c)
    if "xk" in cache:
        h = h + _decode_cross_attention(
            p["xattn"], rms_norm(h, p["ln_x"], cfg.norm_eps),
            cache["xk"][layer], cache["xv"][layer], cfg)
    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        f_out, aux = moe_ffn(p["moe"], hn, cfg, experts=experts, layer=layer)
        # routing counters: routes per expert, and distinct experts a step
        cache["routed"] = cache["routed"].at[layer].add(aux["routed"])
        cache["touched"] = cache["touched"].at[layer].add(
            jnp.sum(aux["routed"] > 0, dtype=jnp.int32))
    else:
        f_out = ffn(p["ffn"], hn)
    return h + f_out, cache


# ---------------------------------------------------------------------------
# Group runners (scan over stacked layers)
# ---------------------------------------------------------------------------
def _remat(fn, cfg: ArchConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    if cfg.remat_policy == "save_attn":
        # keep each layer's attention output; recompute only the FFN half —
        # halves the backward's FSDP re-gathers at ~(B,S,d) saved per layer
        policy = jax.checkpoint_policies.save_only_these_names("attn_out")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save nothing


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_scan(cfg: ArchConfig, gparams):
    """What of a group's params its layer scan runs over, and ``held(i)``:
    the keyword arguments the pattern's layer i takes beside its params
    and its repeat's index ``layer`` (the scan's other ``xs``).

    A dropless MoE layer's expert weights stay out of the scan, whole:
    its grouped matmul takes them as a kernel operand, and a per-layer
    slice of the stacked weights would make XLA copy all of a layer's
    experts at every step (it cannot fuse a slice into the kernel).  The
    body hands them on stacked, as ``experts``, with the repeat's index
    as ``layer``."""
    if cfg.moe is None or not cfg.moe.dropless:
        return gparams, lambda i: {}
    held = {k: {w: p["moe"][w] for w in EXPERT_WEIGHTS}
            for k, p in gparams.items()}
    rest = {k: dict(p, moe={w: v for w, v in p["moe"].items()
                            if w not in EXPERT_WEIGHTS})
            for k, p in gparams.items()}
    return rest, lambda i: {"experts": held[f"p{i}"]}


def run_groups(params_groups, cfg: ArchConfig, h, *, causal=True,
               positions=None, enc_out=None, collect_cache=False):
    """Apply all block groups. Returns (h, aux_total, caches|None)."""
    aux = _aux_zeros()
    caches = []
    for (pattern, repeats), gparams in zip(cfg.block_groups, params_groups):
        gparams, held = _layer_scan(cfg, gparams)

        def body(carry, xs):
            hh, av = carry
            p, r = xs
            hh = constrain(hh, "act_batch", "act_res_seq", None)
            cache_out = {}
            for i, kind in enumerate(pattern):
                hh, a, c = apply_layer(
                    cfg, kind, p[f"p{i}"], hh, causal=causal,
                    positions=positions, enc_out=enc_out, layer=r, **held(i))
                av = av + a
                cache_out[f"p{i}"] = c
            return (hh, av), (cache_out if collect_cache else None)

        (h, aux), cache_g = jax.lax.scan(
            _remat(body, cfg), (h, aux), (gparams, jnp.arange(repeats)))
        caches.append(cache_g)
    return h, aux, (caches if collect_cache else None)


def run_groups_decode(params_groups, cfg: ArchConfig, h, cache_groups, pos):
    """One token through all block groups.  Each group's stacked cache is
    carried through its layer scan whole and written where it lies (a K/V
    slot, a recurrent state, a counter of the repeat the body runs); the
    body's attention reads its repeat's K/V out of the stack."""
    new_caches = []
    for (pattern, repeats), gparams, gcache in zip(
            cfg.block_groups, params_groups, cache_groups):
        gparams, held = _layer_scan(cfg, gparams)

        def body(carry, xs):
            hh, c = carry
            p, r = xs
            c = dict(c)
            for i, kind in enumerate(pattern):
                hh, c[f"p{i}"] = decode_apply_layer(
                    cfg, kind, p[f"p{i}"], hh, c[f"p{i}"], pos, r, **held(i))
            return (hh, c), None

        (h, gcache), _ = jax.lax.scan(
            body, (h, gcache), (gparams, jnp.arange(repeats)))
        new_caches.append(gcache)
    return h, new_caches


# ---------------------------------------------------------------------------
# Embedding / unembedding / encoder
# ---------------------------------------------------------------------------
def embed_tokens(params, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    e = jnp.take(params["embed"], tokens, axis=0).astype(COMPUTE_DTYPE)
    return constrain(e, "act_batch", "act_seq", None)


def unembed(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(h.dtype))
    return constrain(logits, "act_batch", "act_seq", "act_vocab")


def run_encoder(params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over stubbed modality-frontend embeddings."""
    enc = params["encoder"]
    h = frames.astype(COMPUTE_DTYPE)
    h = constrain(h, "act_batch", "act_seq", None)
    positions = jnp.arange(h.shape[1])

    def body(carry, xs):
        hh, av = carry
        hh = constrain(hh, "act_batch", "act_res_seq", None)
        hh, a, _ = apply_layer(cfg, "global", xs, hh, causal=False,
                               positions=positions, enc_out=None)
        return (hh, av + a), None

    (h, _), _ = jax.lax.scan(_remat(body, cfg), (h, _aux_zeros()), enc["blocks"])
    return rms_norm(h, enc["ln_f"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Full forward passes
# ---------------------------------------------------------------------------
def forward(params, cfg: ArchConfig, batch: dict, *, collect_cache=False):
    """Training/prefill forward.

    batch: tokens (B, S) [+ patches (B, P, d) | frames (B, Se, d)].
    Returns (h_final, aux, caches|None).  h_final is final-normed.
    """
    tokens = batch["tokens"]
    h = embed_tokens(params, cfg, tokens)
    enc_out = None
    if cfg.enc_dec:
        enc_out = run_encoder(params, cfg, batch["frames"])
    if cfg.n_patches and "patches" in batch:
        h = jnp.concatenate([batch["patches"].astype(h.dtype), h], axis=1)
        h = constrain(h, "act_batch", "act_seq", None)
    positions = jnp.arange(h.shape[1])
    h, aux, caches = run_groups(
        params["groups"], cfg, h, causal=True, positions=positions,
        enc_out=enc_out, collect_cache=collect_cache)
    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    return h, aux, caches


def decode_step(params, cfg: ArchConfig, cache: dict, tokens: jax.Array):
    """One decode step.  tokens: (B, 1).  Returns (logits, new_cache)."""
    pos = cache["pos"]
    h = embed_tokens(params, cfg, tokens)
    h, new_groups = run_groups_decode(params["groups"], cfg, h, cache["groups"], pos)
    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    logits = unembed(params, cfg, h)
    new_cache = {"pos": pos + 1, "groups": new_groups}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Loss (sequence-chunked cross entropy; bounds logits memory at
# B x loss_chunk x vocab instead of B x S x vocab)
# ---------------------------------------------------------------------------
def lm_loss(params, cfg: ArchConfig, h: jax.Array, labels: jax.Array,
            mask: jax.Array) -> tuple[jax.Array, dict]:
    B, S, _ = h.shape
    chunk = cfg.loss_chunk if cfg.loss_chunk and S % cfg.loss_chunk == 0 else S
    nc = S // chunk

    def ce(hc, lc, mc):
        logits = unembed(params, cfg, hc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(lc, cfg.vocab_size, dtype=jnp.float32)
        lab = jnp.einsum("bsv,bsv->bs", logits, onehot)
        nll = (logz - lab) * mc
        zl = 1e-4 * jnp.square(logz) * mc
        return nll.sum(), zl.sum(), mc.sum()

    if nc == 1:
        nll, zl, cnt = ce(h, labels, mask.astype(jnp.float32))
    else:
        hs = jnp.moveaxis(h.reshape(B, nc, chunk, -1), 1, 0)
        ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
        ms = jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0).astype(jnp.float32)

        def body(carry, xs):
            a, b, c = carry
            n, z, m = jax.checkpoint(ce)(*xs)
            return (a + n, b + z, c + m), None

        (nll, zl, cnt), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (hs, ls, ms))

    cnt = jnp.maximum(cnt, 1.0)
    loss = nll / cnt
    metrics = {"ce_loss": loss, "z_loss": zl / cnt, "tokens": cnt}
    return loss + zl / cnt, metrics


def cast_params(params, dtype=COMPUTE_DTYPE):
    """Compute-precision view of the master weights.

    Casting *before* the layer scan means FSDP all-gathers move bf16, not
    f32 — half the collective traffic and half the gathered-weight memory.
    Gradients flow through the cast back to the f32 masters.
    """
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


def loss_fn(params, cfg: ArchConfig, batch: dict):
    """Scalar training loss. batch needs tokens (B, S+1) (+ frontend stubs)."""
    params = cast_params(params)
    tokens_in = {k: v for k, v in batch.items()}
    tokens_in["tokens"] = batch["tokens"][:, :-1]
    labels = batch["tokens"][:, 1:]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    h, aux, _ = forward(params, cfg, tokens_in)
    if cfg.n_patches and "patches" in batch:
        h = h[:, cfg.n_patches:]  # only text positions predict tokens
    loss, metrics = lm_loss(params, cfg, h, labels, mask)
    n_layers_f = float(max(cfg.count_kind(*ATTN_KINDS), 1))
    if cfg.moe is not None:
        lb, zl, dropped = aux[0], aux[1], aux[2]
        loss = loss + (lb + zl) / n_layers_f
        metrics = dict(metrics, moe_lb_loss=lb / n_layers_f,
                       moe_z_loss=zl / n_layers_f,
                       moe_dropped=dropped / n_layers_f)
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Cache construction (decode) + logical axes for sharding
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, seq_len: int, enc_len: int = 0):
    groups = []
    for pattern, repeats in cfg.block_groups:
        g = {}
        for i, kind in enumerate(pattern):
            if kind == "rwkv":
                ent = recurrent.rwkv_init_state(cfg, batch)
            elif kind == "rglru":
                ent = recurrent.rglru_init_state(cfg, batch)
            else:
                L = cfg.kv_cache_len(kind, seq_len)
                # K/V (L, KV, B, D), as prefill stores them
                ent = {
                    "k": jnp.zeros((L, cfg.n_kv_heads, batch, cfg.d_head), COMPUTE_DTYPE),
                    "v": jnp.zeros((L, cfg.n_kv_heads, batch, cfg.d_head), COMPUTE_DTYPE),
                }
                if cfg.enc_dec:
                    se = enc_len or seq_len
                    ent["xk"] = jnp.zeros((batch, se, cfg.n_kv_heads, cfg.d_head), COMPUTE_DTYPE)
                    ent["xv"] = jnp.zeros((batch, se, cfg.n_kv_heads, cfg.d_head), COMPUTE_DTYPE)
                if cfg.moe is not None:
                    ent["routed"] = jnp.zeros((cfg.moe.n_experts,), jnp.int32)
                    ent["touched"] = jnp.zeros((), jnp.int32)
            g[f"p{i}"] = jax.tree_util.tree_map(
                lambda x, r=repeats: jnp.zeros((r,) + x.shape, x.dtype), ent)
        groups.append(g)
    return {"pos": jnp.zeros((), jnp.int32), "groups": groups}


def extend_cache(cfg: ArchConfig, caches: list, length: int) -> list:
    """Size prefill's K/V caches (stacked (layers, S, KV, B, D)) for
    ``length`` positions: every global-attention layer's to ``length``
    slots, every windowed ring to the smaller of the window and
    ``length``.  The empty slots are masked by decode until it writes them
    (a ring's prefill positions already sit in their slots p % L).
    Recurrent states keep their size."""
    out = []
    for (pattern, _), g in zip(cfg.block_groups, caches):
        g = dict(g)
        for i, kind in enumerate(pattern):
            if kind not in ATTN_KINDS:
                continue
            ent = dict(g[f"p{i}"])
            want = cfg.kv_cache_len(kind, length)
            for name in ("k", "v"):
                x = ent[name]
                ent[name] = jnp.pad(
                    x, ((0, 0), (0, want - x.shape[1]), (0, 0), (0, 0),
                        (0, 0)))
            g[f"p{i}"] = ent
        out.append(g)
    return out


def cache_axes(cfg: ArchConfig):
    """Logical-axis pytree matching init_cache's structure."""
    kv = ("layers", "cache_seq", "act_kv_heads", "cache_batch", None)
    xkv = ("layers", "cache_batch", "cache_seq", "act_kv_heads", None)
    groups = []
    for pattern, repeats in cfg.block_groups:
        g = {}
        for i, kind in enumerate(pattern):
            if kind == "rwkv":
                ent = {k: ("layers",) + v for k, v in recurrent.rwkv_state_axes(cfg).items()}
            elif kind == "rglru":
                ent = {k: ("layers",) + v for k, v in recurrent.rglru_state_axes(cfg).items()}
            else:
                ent = {"k": kv, "v": kv}
                if cfg.enc_dec:
                    ent["xk"] = xkv
                    ent["xv"] = xkv
                if cfg.moe is not None:
                    ent["routed"] = ("layers", None)
                    ent["touched"] = ("layers",)
            g[f"p{i}"] = ent
        groups.append(g)
    return {"pos": (), "groups": groups}
