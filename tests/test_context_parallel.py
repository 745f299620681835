"""Context-parallel flash attention (shard_map over the TP axis)."""
import textwrap

from tests.conftest import run_subprocess_py


def test_cp_flash_matches_oracle_fwd_and_grads():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_COMPUTE_DTYPE"] = "float32"
        import jax, jax.numpy as jnp
        from repro.kernels import ops, ref
        from repro.parallel.axes import mesh_context, TRAIN_RULES

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        B, S, H, KV, D = 2, 2048, 6, 2, 64  # H=6 % 4 != 0 -> CP path
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B,S,H,D), jnp.float32)
        k = jax.random.normal(ks[1], (B,S,KV,D), jnp.float32)
        v = jax.random.normal(ks[2], (B,S,KV,D), jnp.float32)
        do = jax.random.normal(ks[3], (B,S,H,D), jnp.float32)

        def f(q,k,v):
            return (ops.flash_attention(q,k,v,causal=True) * do).sum()
        with mesh_context(mesh, TRAIN_RULES):
            with mesh:
                o = jax.jit(lambda q,k,v: ops.flash_attention(
                    q,k,v,causal=True))(q,k,v)
                g = jax.jit(jax.grad(f, argnums=(0,1,2)))(q,k,v)
        want = ref.attention_ref(q,k,v,causal=True)
        def fr(q,k,v):
            return (ref.attention_ref(q,k,v,causal=True)*do).sum()
        gw = jax.grad(fr, argnums=(0,1,2))(q,k,v)
        assert float(jnp.max(jnp.abs(o-want))) < 5e-6
        for a,b in zip(g, gw):
            assert float(jnp.max(jnp.abs(a-b))) < 5e-5
        print("OK")
    """)
    r = run_subprocess_py(code, timeout=600)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_cp_inactive_without_mesh():
    """Outside a mesh context, flash_attention must not require shard_map."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2048, 6, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2048, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2048, 2, 64), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True)
    want = ref.attention_ref(q, k, v, causal=True)
    import numpy as np

    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=5e-6)
