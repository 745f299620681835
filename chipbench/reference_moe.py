"""Plain reference of the sparse-expert model the MoE cell serves (the
Mellum2 architecture): float32 at the highest matmul precision,
straightforward ``jax.numpy``, no kernels, no cache.

It imports nothing of the program.  The architecture is the one the
configuration file states: token embedding; per layer a pre-RMSNorm
attention block (q, k, v, o projections without biases, rotary embeddings
on the two halves of each head, softmax attention with grouped key/value
heads: causal and limited to the last ``sliding_window`` positions on a
``sliding_attention`` layer, causal over the whole prefix on a
``full_attention`` layer) and a pre-RMSNorm sparse MLP; a final RMSNorm
and an untied lm head.

Rotary frequencies are theta^(-2i/D) on sliding layers.  On full layers
``rope_parameters["full_attention"]`` is YaRN (arXiv:2309.00071, as
transformers' ``_compute_yarn_parameters`` writes it): the frequencies
blend theta^(-2i/D) and theta^(-2i/D) / factor by a linear ramp between
the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
``original_max_position_embeddings``, and cos and sin are scaled by
``attention_factor``.

The sparse MLP: router logits, softmax, the ``num_experts_per_tok``
largest probabilities renormalised to sum to 1 (``norm_topk_prob``); each
expert, a SwiGLU of width ``moe_intermediate_size``, is applied to every
token and its output weighted by the token's gate for it, which is zero
unless the expert is among the token's top k: exactly the sum over the
routed experts, with no capacity and no token dropped.

``fp8=True`` is the control (``chipbench/reference.py``): every matmul's
operands and the residual stream rounded to float8 (e4m3, one scale per
tensor), the precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights_moe
from chipbench.reference import F32, Q_BLOCK, dot, fp8_round, rms_norm


def frequencies(m: dict, layer_type: str) -> tuple[np.ndarray, float]:
    """(D/2 rotary frequencies, cos/sin scale) of a layer of this type."""
    rp = m["rope_parameters"][layer_type]
    D = m["head_dim"]
    theta = float(rp["rope_theta"])
    inv = theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    if rp["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    assert rp["rope_type"] == "yarn", rp
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return D * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(D // 2) - low) / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv = inv / factor * (1.0 - extrapolation) + inv * extrapolation
    return inv.astype(np.float32), float(rp["attention_factor"])


def rotary(x, inv, scale):
    """x (S, heads, D): rotate the pairs (i, i + D/2) by pos * inv[i]."""
    S, _, D = x.shape
    half = D // 2
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv)[None, :]
    cos = scale * jnp.cos(ang)[:, None, :]
    sin = scale * jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v, window, fp8):
    """Causal attention of one sequence, each query seeing the last
    ``window`` positions (0: all): q (S, H, D), k and v (S, KV, D)."""
    S, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, D)

    @jax.checkpoint
    def block(qb, start):
        s = dot("qkgd,tkd->kgqt", qb, k, fp8) / math.sqrt(D)
        qpos = (start + jnp.arange(qb.shape[0]))[:, None]
        kpos = jnp.arange(S)[None, :]
        keep = qpos >= kpos
        if window:
            keep = keep & (qpos - kpos < window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return dot("kgqt,tkd->qkgd", p, v, fp8)

    outs = [block(qg[i:i + Q_BLOCK], i) for i in range(0, S, Q_BLOCK)]
    return jnp.concatenate(outs, 0).reshape(S, H, D)


def route(h, router, m, fp8=False):
    """(S, E) gates: each token's top-k softmax probabilities, renormalised
    where the configuration says so, and zero for every other expert."""
    probs = jax.nn.softmax(dot("sd,de->se", h, router, fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def experts(h, gates, p, fp8):
    """The sparse MLP of one sequence h (S, d) under ``gates`` (S, E)."""

    def one(acc, xs):
        wg, wu, wd, g = xs
        u = jax.nn.silu(dot("sd,df->sf", h, wg, fp8)) \
            * dot("sd,df->sf", h, wu, fp8)
        return acc + g[:, None] * dot("sf,fd->sd", u, wd, fp8), None

    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, F32),
                          (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return out


def forward(params, tokens, m, fp8=False):
    """(final-normed hidden states (S, d), gates (layers, S, E) in execution
    order) of one sequence ``tokens`` (S,)."""
    eps = m["rms_norm_eps"]
    held = fp8_round if fp8 else (lambda v: v)
    x = held(params["embed"][tokens].astype(F32))
    pattern = weights_moe.period(m)

    def layer(x, p, layer_type):
        a = p["attn"]
        inv, scale = frequencies(m, layer_type)
        window = m["sliding_window"] if layer_type == "sliding_attention" \
            else 0
        h = rms_norm(x, p["ln1"], eps)
        q = rotary(dot("sd,dhk->shk", h, a["wq"], fp8), inv, scale)
        k = rotary(dot("sd,dhk->shk", h, a["wk"], fp8), inv, scale)
        v = dot("sd,dhk->shk", h, a["wv"], fp8)
        x = held(x + dot("shk,hkd->sd", attention(q, k, v, window, fp8),
                         a["wo"], fp8))
        h = rms_norm(x, p["ln2"], eps)
        gates = route(h, p["moe"]["router"], m, fp8)
        return held(x + experts(h, gates, p["moe"], fp8)), gates

    @jax.checkpoint
    def one_period(x, ps):
        gates = []
        for i, layer_type in enumerate(pattern):
            x, g = layer(x, ps[f"p{i}"], layer_type)
            gates.append(g)
        return x, jnp.stack(gates)

    x, gates = jax.lax.scan(one_period, x, params["groups"][0])
    return rms_norm(x, params["ln_f"], eps), gates.reshape(
        -1, *gates.shape[2:])


def make_served_logits(m: dict, fp8: bool = False):
    """(params, prompt (P,), served (T,)) -> logits (T, V) of the positions
    that predicted each served token, the prompt and the served tokens
    before it given (teacher forcing)."""

    def fn(params, prompt, served):
        toks = jnp.concatenate([prompt, served[:-1]])
        h, _ = forward(params, toks, m, fp8)
        return dot("sd,dv->sv", h[prompt.shape[0] - 1:], params["lm_head"],
                   fp8)

    return jax.jit(fn)
