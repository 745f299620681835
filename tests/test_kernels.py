"""Kernel correctness: Pallas (interpret=True) and blockwise-jnp paths vs
the pure-jnp oracles in kernels/ref.py, swept over shapes/dtypes/modes."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as flash_pallas
from repro.kernels.rglru_scan import rglru as rglru_pallas
from repro.kernels.rwkv6_scan import wkv6 as wkv6_pallas
from tests.conftest import run_subprocess_py

KEY = jax.random.PRNGKey(0)


def _qkv(B, S, H, KV, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32).astype(dtype)
    return q, k, v


SWEEP = [
    # (B, S, H, KV, D, causal, window, chunk)
    (2, 256, 4, 2, 64, True, 0, 0),
    (1, 512, 4, 4, 64, False, 0, 0),
    (1, 512, 8, 1, 64, True, 0, 0),      # MQA
    (1, 1024, 4, 2, 64, True, 256, 0),   # sliding window
    (1, 1024, 2, 2, 64, True, 0, 256),   # chunked
    (2, 256, 4, 4, 128, True, 0, 0),     # d_head 128
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", SWEEP)
def test_pallas_flash_matches_oracle(case, dtype):
    B, S, H, KV, D, causal, window, chunk = case
    q, k, v = _qkv(B, S, H, KV, D, dtype)
    got = flash_pallas(q, k, v, causal=causal, window=window, chunk=chunk,
                       block_q=128, block_k=128, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             chunk=chunk)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("case", SWEEP[:4])
def test_jnp_flash_matches_oracle(case):
    B, S, H, KV, D, causal, window, chunk = case
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    got = ops._flash(q, k, v, causal, window, chunk, 0.0, 0, 128, 128)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("case", [SWEEP[0], SWEEP[2], SWEEP[3], SWEEP[4]])
def test_pallas_flash_vjp_matches_oracle_grads(case):
    """Pallas forward (+ lse) with the blockwise-jnp backward."""
    B, S, H, KV, D, causal, window, chunk = case
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    do = jax.random.normal(KEY, (B, S, H, D), jnp.float32)

    def f_pl(q, k, v):
        return (flash_pallas(q, k, v, causal=causal, window=window,
                             chunk=chunk, block_q=128, block_k=128,
                             interpret=True) * do).sum()

    def f_ref(q, k, v):
        return (ref.attention_ref(q, k, v, causal=causal, window=window,
                                  chunk=chunk) * do).sum()

    g1 = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_pallas_flash_sharded_under_mesh_subprocess():
    """Under a mesh the kernel runs per (batch, head) shard via shard_map;
    forward and grads match the oracle, with and without head sharding."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.kernels import ops, ref
        from repro.parallel.axes import mesh_context, TRAIN_RULES
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for H, KV in ((4, 2), (3, 1)):   # heads sharded / batch only
            ks = jax.random.split(jax.random.PRNGKey(0), 4)
            q = jax.random.normal(ks[0], (2, 256, H, 64), jnp.float32)
            k = jax.random.normal(ks[1], (2, 256, KV, 64), jnp.float32)
            v = jax.random.normal(ks[2], (2, 256, KV, 64), jnp.float32)
            do = jax.random.normal(ks[3], (2, 256, H, 64), jnp.float32)
            f = lambda q, k, v: (ops.pallas_flash(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=True) * do).sum()
            fr = lambda q, k, v: (ref.attention_ref(q, k, v) * do).sum()
            with mesh_context(mesh, TRAIN_RULES), jax.set_mesh(mesh):
                o = jax.jit(lambda q, k, v: ops.pallas_flash(
                    q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=True))(q, k, v)
                g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
            np.testing.assert_allclose(o, ref.attention_ref(q, k, v),
                                       atol=2e-6)
            for a, b in zip(g, jax.grad(fr, argnums=(0, 1, 2))(q, k, v)):
                np.testing.assert_allclose(a, b, atol=5e-5)
        print("OK")
    """)
    r = run_subprocess_py(code, timeout=600)
    assert "OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.parametrize("case", [SWEEP[0], SWEEP[3], SWEEP[4]])
def test_flash_custom_vjp_matches_oracle_grads(case):
    B, S, H, KV, D, causal, window, chunk = case
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    do = jax.random.normal(KEY, (B, S, H, D), jnp.float32)

    def f_fl(q, k, v):
        return (ops._flash(q, k, v, causal, window, chunk, 0.0, 0,
                           128, 128) * do).sum()

    def f_ref(q, k, v):
        return (ref.attention_ref(q, k, v, causal=causal, window=window,
                                  chunk=chunk) * do).sum()

    g1 = jax.grad(f_fl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_softcap():
    B, S, H, KV, D = 1, 256, 2, 2, 64
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    got = ops._flash(q, k, v, True, 0, 0, 30.0, 0, 128, 128)
    want = ref.attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_decode_attention_consistent_with_full():
    """Decoding position S-1 against a cache must equal full attention."""
    B, S, H, KV, D = 2, 128, 4, 2, 64
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    full = ref.attention_ref(q, k, v, causal=True)
    slot_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    pos = jnp.full((B,), S - 1)
    dec = ref.decode_attention_ref(q[:, -1:], k, v, slot_pos, pos)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]),
                               atol=2e-5)


@pytest.mark.parametrize("with_state", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 2, 16), (2, 256, 4, 32),
                                   (1, 64, 8, 64), (2, 1, 4, 32)])
def test_pallas_wkv6_matches_oracle(shape, dtype, with_state):
    B, S, H, D = shape
    ks = jax.random.split(KEY, 6)
    r = (jax.random.normal(ks[0], (B, S, H, D)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, H, D)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, H, D)) * 0.5).astype(dtype)
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, D))) * 0.5
         + 0.45).astype(dtype)
    u = (jax.random.normal(ks[4], (H, D)) * 0.3).astype(dtype)
    s0 = (jax.random.normal(ks[5], (B, H, D, D)) * 0.3 if with_state
          else None)
    got, s_got = wkv6_pallas(r, k, v, w, u, s0, chunk=32, interpret=True)
    want, s_want = ref.wkv6_ref(r, k, v, w, u, s0)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               atol=tol)


@pytest.mark.parametrize("shape", [(1, 128, 64), (2, 256, 128), (1, 64, 512)])
def test_pallas_rglru_matches_oracle(shape):
    B, S, W = shape
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (B, S, W), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, W)))
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    got, h_got = rglru_pallas(x, la, h0, chunk=64, block_w=64, interpret=True)
    want, h_want = ref.rglru_ref(x, la, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want),
                               atol=1e-5)


def test_ops_rglru_associative_scan_matches_ref():
    B, S, W = 2, 192, 96
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (B, S, W), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, W)))
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    got, h_got = ops.rglru(x, la, h0)
    want, h_want = ref.rglru_ref(x, la, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want), atol=1e-4)


def test_causal_conv1d_state_continuity():
    """conv over a split sequence with carried state == conv over the whole."""
    B, S, W, K = 2, 64, 16, 4
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (B, S, W), jnp.float32)
    w = jax.random.normal(ks[1], (K, W), jnp.float32)
    full, _ = ops.causal_conv1d(x, w)
    a, st = ops.causal_conv1d(x[:, :40], w)
    b, _ = ops.causal_conv1d(x[:, 40:], w, st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b], 1)),
                               np.asarray(full), atol=1e-6)


def test_wkv6_state_continuity():
    """wkv over split sequence with carried state == whole sequence."""
    B, S, H, D = 1, 64, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, D)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, D))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, D)) * 0.3
    full, s_full = ref.wkv6_ref(r, k, v, w, u)
    a, st = ref.wkv6_ref(r[:, :40], k[:, :40], v[:, :40], w[:, :40], u)
    b, s_b = ref.wkv6_ref(r[:, 40:], k[:, 40:], v[:, 40:], w[:, 40:], u, st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b], 1)),
                               np.asarray(full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_b), np.asarray(s_full), atol=1e-5)
