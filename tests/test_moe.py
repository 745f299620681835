"""The dropless expert layer, the grouped matmul under it, and rotary
embeddings by layer kind (plain RoPE, YaRN on global layers)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoESpec, YaRNSpec, get_arch
from repro.kernels import ops
from repro.models import layers

# (rows per group): empty groups first, inside and last; one group alone
GROUPS = [(3, 0, 10, 7, 0), (0, 0, 5), (16,), (1, 2, 0, 0, 4, 9)]


def _dense_groups(x, w, sizes):
    ends = np.cumsum(sizes)
    out = np.zeros((x.shape[0], w.shape[2]), np.float64)
    for i, (s, e) in enumerate(zip(ends - np.asarray(sizes), ends)):
        out[s:e] = x[s:e].astype(np.float64) @ w[i].astype(np.float64)
    return out


@pytest.mark.parametrize("sizes", GROUPS)
@pytest.mark.parametrize("path", ["ragged_dot", "pallas_interpret"])
def test_grouped_matmul_equals_a_matmul_per_group(sizes, path):
    rng = np.random.default_rng(len(sizes))
    m, k, n = sum(sizes), 32, 48
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(len(sizes), k, n)).astype(np.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    if path == "ragged_dot":
        got = ops.grouped_matmul(jnp.asarray(x), jnp.asarray(w), gs)
    else:
        got = ops.pallas_gmm(jnp.asarray(x), jnp.asarray(w), gs,
                             interpret=True)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), _dense_groups(x, w, sizes),
                               rtol=1e-5, atol=1e-4)


def test_pallas_gmm_tiles_fit_the_dims():
    # a k or n tile divides its dim, a multiple of 128 where the dim is one
    assert ops._gmm_tile(2304) == 1152 and ops._gmm_tile(896) == 896
    assert ops._gmm_tile(1792) == 896 and ops._gmm_tile(48) == 48
    assert ops._gmm_tile(8192) == 1024


def _moe_cfg(E=8, K=2, d=32, f=24):
    return ArchConfig(name="t", family="moe", n_layers=1, d_model=d,
                      n_heads=2, n_kv_heads=1, d_head=16, d_ff=f,
                      vocab_size=64, block_groups=((("global",), 1),),
                      moe=MoESpec(n_experts=E, top_k=K,
                                  capacity_factor=None))


@pytest.mark.parametrize("stacked", [False, True])
def test_dropless_layer_equals_a_loop_over_experts(stacked):
    """Every route of every token is computed (none dropped) and weighted
    by its renormalised top-k gate: the sum a plain loop over each
    expert's own tokens gives.  Stacked, the layer takes every repeat's
    experts apart from its params, and reads only its own repeat's."""
    cfg = _moe_cfg()
    rng = np.random.default_rng(0)
    E, d, f = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    p = {"router": rng.normal(size=(d, E)),
         "w_gate": rng.normal(size=(E, d, f)) / math.sqrt(d),
         "w_up": rng.normal(size=(E, d, f)) / math.sqrt(d),
         "w_down": rng.normal(size=(E, f, d)) / math.sqrt(f)}
    p = {k: v.astype(np.float32) for k, v in p.items()}
    x = rng.normal(size=(2, 13, d)).astype(np.float32)
    if stacked:  # repeat 1 of 3; the other repeats' experts are noise
        experts = {k: jnp.asarray(np.stack(
            [rng.normal(size=v.shape).astype(np.float32), v,
             rng.normal(size=v.shape).astype(np.float32)]))
            for k, v in p.items() if k != "router"}
        out, aux = layers.moe_ffn({"router": jnp.asarray(p["router"])},
                                  jnp.asarray(x), cfg, experts=experts,
                                  layer=jnp.int32(1))
    else:
        out, aux = layers.moe_ffn({k: jnp.asarray(v) for k, v in p.items()},
                                  jnp.asarray(x), cfg)

    xt = x.reshape(-1, d).astype(np.float64)
    logits = xt @ p["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1)[:, :cfg.moe.top_k]
    want = np.zeros_like(xt)
    routed = np.zeros(E, np.int64)
    for e in range(E):
        rows, slot = np.nonzero(top == e)
        routed[e] = len(rows)
        if not len(rows):
            continue
        h = xt[rows] @ p["w_gate"][e]
        h = h / (1 + np.exp(-h)) * (xt[rows] @ p["w_up"][e])
        gate = probs[rows, e] / probs[rows[:, None], top[rows]].sum(-1)
        want[rows] += gate[:, None] * (h @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, d), want,
                               rtol=2e-4, atol=2e-4)
    assert np.array_equal(np.asarray(aux["routed"]), routed)
    assert float(aux["moe_dropped_frac"]) == 0.0


def test_mellum_is_dropless_and_the_capacity_archs_are_not():
    assert get_arch("mellum2-12b").moe.dropless
    for name in ("mixtral-8x22b", "llama4-scout-17b-a16e"):
        assert not get_arch(name).moe.dropless


def _yarn_numpy(theta, D, y):
    """transformers' ``_compute_yarn_parameters``, in numpy."""
    pos_freqs = theta ** (np.arange(0, D, 2) / D)
    extra, inter = 1.0 / pos_freqs, 1.0 / (y.factor * pos_freqs)

    def corr(rot):
        return (D * math.log(y.original_max_position / (rot * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), D - 1)
    ramp = np.clip((np.arange(D // 2) - low) / (high - low), 0, 1)
    extra_factor = 1 - ramp
    return inter * (1 - extra_factor) + extra * extra_factor


def _rotate_numpy(x, pos, inv, scale):
    # the angle rounded to float32 as the program rounds it (at position
    # 8192 a float32 angle is off by up to 5e-4 radians); the rest float64
    ang = (pos.astype(np.float32)[:, None]
           * inv.astype(np.float32)[None, :]).astype(np.float64)
    cos, sin = scale * np.cos(ang)[:, None], scale * np.sin(ang)[:, None]
    a, b = np.split(x.astype(np.float64), 2, axis=-1)
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def test_yarn_rope_matches_the_formula():
    cfg = get_arch("mellum2-12b")
    y, D = cfg.yarn, cfg.d_head
    inv = _yarn_numpy(cfg.rope_theta, D, y)
    assert np.allclose(layers.yarn_frequencies(cfg.rope_theta, D, y), inv,
                       rtol=1e-6)
    # the fastest dimensions keep their frequency, the slowest are divided
    # by the factor
    assert inv[0] == 1.0
    assert inv[-1] == pytest.approx(
        cfg.rope_theta ** (-(D - 2) / D) / y.factor)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(1, 9, 2, D)).astype(np.float32)
    pos = np.array([0, 1, 2, 100, 1023, 1024, 4095, 4224, 8192])
    got = layers.rope(jnp.asarray(x), jnp.asarray(pos), cfg.rope_theta, y)
    want = _rotate_numpy(x[0], pos, inv, y.attention_factor)
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4,
                               atol=2e-4)
    # a local layer of the same model rotates without YaRN (its float32
    # frequencies, computed on the device, are an ulp off the float64 ones:
    # 1e-3 radians at position 8192)
    plain = layers.rope(jnp.asarray(x), jnp.asarray(pos), cfg.rope_theta)
    np.testing.assert_allclose(
        np.asarray(plain)[0],
        _rotate_numpy(x[0], pos, cfg.rope_theta ** (-np.arange(0, D, 2) / D),
                      1.0),
        rtol=2e-3, atol=2e-3)


def _rope_before(x, positions, theta):
    """``layers.rope`` as it was before YaRN, for every model without it."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("arch", ["starcoder2-3b", "granite-20b",
                                  "gemma3-4b"])
def test_dense_rope_is_unchanged(arch):
    cfg = get_arch(arch)
    assert cfg.yarn is None
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, cfg.d_head),
                          jnp.bfloat16)
    pos = jnp.arange(7) + 4090
    args = (x, pos, cfg.rope_theta)
    assert np.array_equal(np.asarray(layers.rope(*args), np.float32),
                          np.asarray(_rope_before(*args), np.float32))
    assert str(jax.make_jaxpr(layers.rope, static_argnums=2)(*args)) == \
        str(jax.make_jaxpr(_rope_before, static_argnums=2)(*args))


def test_yarn_spec_defaults_leave_rope_alone():
    # factor 1 with attention factor 1 is plain RoPE
    y = YaRNSpec(factor=1.0, original_max_position=4096)
    inv = layers.yarn_frequencies(10_000.0, 64, y)
    assert np.allclose(inv, 10_000.0 ** (-np.arange(0, 64, 2) / 64),
                       rtol=1e-6)
