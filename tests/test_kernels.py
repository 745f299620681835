"""Kernel correctness: Pallas (interpret=True) and blockwise-jnp paths vs
the pure-jnp oracles in kernels/ref.py, swept over shapes/dtypes/modes."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fak
from repro.kernels import ops, ref
from repro.kernels.flash_attention import block_class
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
    # (B, S, H, KV, D, causal, window, chunk, softcap)
    (2, 256, 4, 2, 64, True, 0, 0, 0.0),
    (1, 512, 4, 4, 64, False, 0, 0, 0.0),
    (1, 512, 8, 1, 64, True, 0, 0, 0.0),      # MQA
    (1, 1024, 4, 2, 64, True, 256, 0, 0.0),   # sliding window
    (1, 1024, 2, 2, 64, True, 0, 256, 0.0),   # chunked
    (2, 256, 4, 4, 128, True, 0, 0, 0.0),     # d_head 128
    # at block 128, a window and chunks that are not multiples of the
    # block: their edges cross tiles off the diagonal
    (1, 1024, 4, 2, 64, True, 200, 0, 0.0),
    (1, 1024, 2, 2, 64, True, 0, 192, 0.0),
    (1, 768, 2, 1, 64, False, 200, 192, 0.0),  # full, masked, skipped
    (1, 64, 4, 2, 128, True, 0, 0, 0.0),      # one block, under 128 wide
    (1, 512, 4, 2, 64, True, 0, 0, 2.0),      # softcap
]


def _lse_ref(q, k, *, causal, window, chunk, softcap):
    """The oracle's per-row log-sum-exp, (B, H, Sq, 1) with h = kv * G + g."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, H // KV, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32)) / np.sqrt(D)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    m = ref._mask(jnp.arange(Sq), jnp.arange(Sk), causal=causal,
                  window=window, chunk=chunk)
    s = jnp.where(m, s, ref.NEG_INF)
    return jax.nn.logsumexp(s, axis=-1).reshape(B, H, Sq, 1)


# the two forwards: (o, lse) at blocks of 128
FORWARDS = {
    "pallas": lambda q, k, v, **kw: fak.pallas_forward(
        q, k, v, block_q=128, block_k=128, interpret=True, **kw),
    "blockwise": lambda q, k, v, **kw: fak.blockwise_forward(
        q, k, v, block_q=128, block_k=128, **kw),
}


@pytest.mark.parametrize("forward", list(FORWARDS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", SWEEP)
def test_pallas_flash_matches_oracle(case, dtype, forward):
    """Each forward's o and lse against the oracle: the backward reads
    either forward's lse."""
    B, S, H, KV, D, causal, window, chunk, softcap = case
    q, k, v = _qkv(B, S, H, KV, D, dtype)
    mode = dict(causal=causal, window=window, chunk=chunk, softcap=softcap)
    got, lse = FORWARDS[forward](q, k, v, **mode)
    want = ref.attention_ref(q, k, v, **mode)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert lse.shape == (B, H, S, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_lse_ref(q, k, **mode)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("causal,window,chunk", [
    (True, 0, 0), (False, 0, 0),
    (True, 256, 0), (True, 200, 0), (True, 100, 0), (False, 200, 0),
    (True, 0, 256), (True, 0, 192), (True, 0, 100), (False, 0, 192),
    (True, 200, 192), (False, 200, 192),
])
def test_flash_block_class_matches_element_mask(causal, window, chunk):
    """The kernel's tile sorting against a brute-force element mask: a
    *full* tile has every pair visible, a *skipped* one none, and every
    other tile takes the masked body."""
    S, blk = 1024, 128
    pos = jnp.arange(S)
    mask = np.asarray(ref._mask(pos, pos, causal=causal, window=window,
                                chunk=chunk))
    seen = set()
    for qs in range(0, S, blk):
        for ks in range(0, S, blk):
            tile = mask[qs:qs + blk, ks:ks + blk]
            run, full = block_class(qs, ks, blk, blk, causal, window, chunk)
            assert full == tile.all(), (qs, ks)
            if not run:
                assert not tile.any(), (qs, ks)
            seen.add("full" if full else "masked" if run else "skipped")
    if causal:   # the diagonal is crossed, the far past is never seen
        assert {"masked", "skipped"} <= seen
    if not (causal or window or chunk):
        assert seen == {"full"}


# the two differentiable wrappers, one custom_vjp: the forward differs,
# the backward is the same
ATTENTIONS = {
    "pallas": lambda q, k, v, **kw: fak.flash_attention(
        q, k, v, block_q=128, block_k=128, interpret=True, **kw),
    "blockwise": lambda q, k, v, **kw: fak.blockwise_attention(
        q, k, v, block_q=128, block_k=128, **kw),
}


@pytest.mark.parametrize("forward", list(ATTENTIONS))
@pytest.mark.parametrize("case", [SWEEP[0], SWEEP[2], SWEEP[3], SWEEP[4],
                                  *SWEEP[6:]])
def test_pallas_flash_vjp_matches_oracle_grads(case, forward):
    """Either forward (+ lse) with the blockwise-jnp backward."""
    B, S, H, KV, D, causal, window, chunk, softcap = case
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    do = jax.random.normal(KEY, (B, S, H, D), jnp.float32)
    mode = dict(causal=causal, window=window, chunk=chunk, softcap=softcap)

    def f_fl(q, k, v):
        return (ATTENTIONS[forward](q, k, v, **mode) * do).sum()

    def f_ref(q, k, v):
        return (ref.attention_ref(q, k, v, **mode) * do).sum()

    g1 = jax.grad(f_fl, argnums=(0, 1, 2))(q, k, v)
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


def test_flash_under_mesh_with_indivisible_heads_subprocess():
    """6 heads on a 4-way model axis, which they do not divide: under a mesh
    the blockwise path is partitioned by XLA like any other op, and its
    forward and grads match the oracle."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.kernels import ops, ref
        from repro.parallel.axes import mesh_context, TRAIN_RULES

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        B, S, H, KV, D = 2, 2048, 6, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        do = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

        def f(q, k, v):
            return (ops.flash_attention(q, k, v, causal=True) * do).sum()
        with mesh_context(mesh, TRAIN_RULES), jax.set_mesh(mesh):
            o = jax.jit(lambda q, k, v: ops.flash_attention(
                q, k, v, causal=True))(q, k, v)
            g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
        want = ref.attention_ref(q, k, v, causal=True)
        def fr(q, k, v):
            return (ref.attention_ref(q, k, v, causal=True) * do).sum()
        gw = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        assert float(jnp.max(jnp.abs(o - want))) < 5e-6
        for a, b in zip(g, gw):
            assert float(jnp.max(jnp.abs(a - b))) < 5e-5
        print("OK")
    """)
    r = run_subprocess_py(code, timeout=600)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_cp_inactive_without_mesh():
    """Outside a mesh context, flash_attention at 2048 takes the blockwise
    path and needs no shard_map."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2048, 6, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2048, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2048, 2, 64), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=5e-6)


def test_flash_softcap():
    B, S, H, KV, D = 1, 256, 2, 2, 64
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    got = fak.blockwise_attention(q, k, v, causal=True, softcap=30.0,
                                  block_q=128, block_k=128)
    want = ref.attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_decode_attention_consistent_with_full():
    """Decoding position S-1 against a cache must equal full attention."""
    B, S, H, KV, D = 2, 128, 4, 2, 64
    q, k, v = _qkv(B, S, H, KV, D, jnp.float32)
    full = ref.attention_ref(q, k, v, causal=True)
    slot_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    pos = jnp.full((B,), S - 1)
    # the cache holds (L, KV, B, D), the order decode stores
    dec = ref.decode_attention_ref(q[:, -1:], k.transpose(1, 2, 0, 3),
                                   v.transpose(1, 2, 0, 3), slot_pos, pos)
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
