"""Plain reference of the model the cells run: float32 at the highest
matmul precision, straightforward ``jax.numpy``, no kernels, no cache.

It imports nothing of the program.  The architecture is the one the
configuration files state: token embedding; per layer a pre-RMSNorm
attention block (q, k, v, o projections without biases, rotary
embeddings on the two halves of each head, causal softmax attention with
grouped key/value heads) and a pre-RMSNorm MLP (up projection, tanh-GELU,
down projection); a final RMSNorm and an untied lm head.  Training adds
the mean cross entropy plus the z-loss ``z * mean(logsumexp^2)`` and
AdamW with global-norm clipping, linear warm-up and cosine decay, as the
configuration's ``optimizer`` states.

To fit one chip, attention runs over blocks of queries and every layer
and query block is rematerialized; the math is unchanged.

``fp8=True`` is the control: every matmul's operands and the residual
stream are rounded to float8 (e4m3, one scale per tensor), and in
training their gradients to e5m2: the precision below the bfloat16 the
configurations compute in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512
LOSS_BLOCK = 1024


def _round(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def fp8_round(x):
    """x rounded to e4m3 with one scale per tensor; its gradient is rounded
    to e5m2 with a scale of its own, as float8 training does."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8_round(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, 57344.0),)


fp8_round.defvjp(_fp8_fwd, _fp8_bwd)


def dot(spec, a, b, fp8=False):
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        a, b = fp8_round(a), fp8_round(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rotary(x, theta):
    """x (S, heads, D): rotate the pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v, fp8):
    """Causal attention of one sequence: q (S, H, D), k and v (S, KV, D)."""
    S, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, D)

    @jax.checkpoint
    def block(qb, start):
        s = dot("qkgd,tkd->kgqt", qb, k, fp8) / math.sqrt(D)
        keep = (start + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(S)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return dot("kgqt,tkd->qkgd", p, v, fp8)

    outs = [block(qg[i:i + Q_BLOCK], i) for i in range(0, S, Q_BLOCK)]
    return jnp.concatenate(outs, 0).reshape(S, H, D)


def hidden(params, tokens, m, fp8=False):
    """Final-normed hidden states of one sequence ``tokens`` (S,)."""
    eps, theta = m["norm_epsilon"], m["rope_theta"]
    # the control holds the residual stream in float8 where the program
    # holds it in bfloat16
    held = fp8_round if fp8 else (lambda v: v)
    x = held(params["embed"][tokens].astype(F32))

    @jax.checkpoint
    def layer(x, p):
        a = p["attn"]
        h = rms_norm(x, p["ln1"], eps)
        q = rotary(dot("sd,dhk->shk", h, a["wq"], fp8), theta)
        k = rotary(dot("sd,dhk->shk", h, a["wk"], fp8), theta)
        v = dot("sd,dhk->shk", h, a["wv"], fp8)
        x = held(x + dot("shk,hkd->sd", attention(q, k, v, fp8), a["wo"],
                         fp8))
        h = rms_norm(x, p["ln2"], eps)
        u = gelu_tanh(dot("sd,df->sf", h, p["ffn"]["w_up"], fp8))
        return held(x + dot("sf,fd->sd", u, p["ffn"]["w_down"], fp8)), None

    x, _ = jax.lax.scan(layer, x, params["groups"][0]["p0"])
    return rms_norm(x, params["ln_f"], eps)


def logits(params, h, fp8=False):
    return dot("sd,dv->sv", h, params["lm_head"], fp8)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def _row_sums(params, tokens, m, fp8):
    """(sum of token NLLs, sum of logsumexp^2) of one row (S + 1,)."""
    h = hidden(params, tokens[:-1], m, fp8)
    labels = tokens[1:]

    @jax.checkpoint
    def chunk(hc, lc):
        lg = logits(params, hc, fp8)
        lz = jax.nn.logsumexp(lg, -1)
        lab = jnp.take_along_axis(lg, lc[:, None], -1)[:, 0]
        return jnp.sum(lz - lab), jnp.sum(lz * lz)

    parts = [chunk(h[i:i + LOSS_BLOCK], labels[i:i + LOSS_BLOCK])
             for i in range(0, h.shape[0], LOSS_BLOCK)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def make_loss(m: dict, fp8: bool = False):
    z = m["optimizer"]["z_loss"]
    rows = jax.vmap(_row_sums, in_axes=(None, 0, None, None))

    def loss(params, tokens):  # tokens (B, S + 1)
        nll, zz = rows(params, tokens, m, fp8)
        total = jnp.sum(nll) + z * jnp.sum(zz)
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))

    return loss


def lr_at(opt: dict, step, total_steps: int):
    warm = opt["warmup_steps"]
    frac = jnp.clip((step - warm) / max(total_steps - warm, 1), 0.0, 1.0)
    decay = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) \
        * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return opt["lr"] * jnp.minimum(step / max(warm, 1), 1.0) * decay


@jax.jit
def _clip_scale(grads, clip):
    leaves = jax.tree_util.tree_leaves(grads)
    norms = jnp.stack([jnp.linalg.norm(x.ravel()) for x in leaves])
    total = jnp.sqrt(jnp.sum(norms * norms))
    return jnp.minimum(1.0, clip / jnp.maximum(total, 1e-9)), norms


def _adam_leaf(p, g, mo, ve, scale, lr, c1, c2, opt):
    g = g * scale
    mo = opt["b1"] * mo + (1 - opt["b1"]) * g
    ve = opt["b2"] * ve + (1 - opt["b2"]) * g * g
    delta = (mo / c1) / (jnp.sqrt(ve / c2) + opt["eps"]) \
        + opt["weight_decay"] * p
    return p - lr * delta, mo, ve


def train_readings(m: dict, seed: int, batches, total_steps: int,
                   fp8: bool = False) -> dict:
    """The reference run through ``len(batches)`` steps from the seed's
    weights: each step's loss, the first step's clipped gradient per leaf
    (as the optimizer applies it) and its raw gradient per leaf, and each
    leaf's change over all the steps.

    To leave room for the gradients, Adam's moments stay on the host
    between steps and visit the device one leaf at a time."""
    opt = m["optimizer"]
    grad_fn = jax.jit(jax.value_and_grad(make_loss(m, fp8)))
    adam = jax.jit(lambda *a: _adam_leaf(*a, opt), donate_argnums=(0, 2, 3))
    params = jax.tree_util.tree_leaves(weights.make(m, seed, F32))
    mom = [np.zeros(p.shape, np.float32) for p in params]
    vel = [np.zeros(p.shape, np.float32) for p in params]
    losses = []
    for i, b in enumerate(batches):
        t = float(i + 1)
        loss, g = grad_fn(params_tree(m, params), jnp.asarray(b))
        g = jax.tree_util.tree_leaves(g)
        scale, norms = _clip_scale(g, opt["grad_clip"])
        lr = lr_at(opt, t, total_steps)
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        for j in range(len(params)):
            params[j], mo, ve = adam(params[j], g[j], jnp.asarray(mom[j]),
                                     jnp.asarray(vel[j]), scale, lr, c1, c2)
            mom[j], vel[j] = np.asarray(mo), np.asarray(ve)
            g[j] = None
        losses.append(float(loss))
        if i == 0:
            grad_raw = np.asarray(norms)
            grad = grad_raw * float(scale)
    del mom, vel
    return {"losses": losses, "grad": grad, "grad_raw": grad_raw,
            "delta": leaf_delta_norms(m, seed, params)}


def params_tree(m: dict, leaves: list):
    """The weight tree of ``weights.layout(m)`` holding ``leaves``."""
    return jax.tree_util.tree_unflatten(weights.tree_of(m), leaves)


def leaf_delta_norms(m: dict, seed: int, params) -> np.ndarray:
    """Per-leaf norm of ``params`` minus the seed's initial weights."""
    out = []
    for i, p in enumerate(jax.tree_util.tree_leaves(params)):
        p0 = weights.leaf(m, seed, i, F32)
        out.append(float(jnp.linalg.norm((p - p0).ravel())))
    return np.array(out)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def make_served_logits(m: dict, fp8: bool = False):
    """(params, prompt (P,), served (T,)) -> logits (T, V) of the positions
    that predicted each served token, the prompt and the served tokens
    before it given (teacher forcing)."""

    def fn(params, prompt, served):
        toks = jnp.concatenate([prompt, served[:-1]])
        h = hidden(params, toks, m, fp8)
        return logits(params, h[prompt.shape[0] - 1:], fp8)

    return jax.jit(fn)


def served_gaps(ref_logits, chosen) -> np.ndarray:
    """Per position, how far the chosen token's reference logit lies below
    the reference's best."""
    ref_logits = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    best = ref_logits.max(-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]
