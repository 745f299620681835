"""Sharding rules, compression, pipeline parallelism, elastic planning.

Multi-device cases run in subprocesses with their own XLA_FLAGS (tests in
this process see the single CPU device by design)."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from tests.conftest import run_subprocess_py
from repro.parallel import compression
from repro.parallel.axes import (LONG_CONTEXT_RULES, SERVE_RULES, TRAIN_RULES,
                                 ShardingRules)
from repro.runtime.elastic import plan_shrink


# -- sharding rules -----------------------------------------------------------
def test_rules_spec_drops_reused_axis():
    r = ShardingRules({"a": "model", "b": "model"})
    spec = r.spec(("a", "b"))
    assert spec == jax.sharding.PartitionSpec("model")


def test_spec_for_divisibility(monkeypatch):
    # shape-aware resolution must drop non-dividing axes (MQA kv=1 etc.)
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.parallel.axes import TRAIN_RULES, spec_for
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        # kv_heads=1 cannot shard over model=4 -> None
        s1 = spec_for((1024, 1, 128), ("embed", "kv_heads", "head_dim"),
                      mesh, TRAIN_RULES)
        assert s1 == jax.sharding.PartitionSpec("data"), s1
        # vocab 256206 % 4 != 0 -> dropped
        s2 = spec_for((256206, 1024), ("vocab", "embed"), mesh, TRAIN_RULES)
        assert s2 == jax.sharding.PartitionSpec(None, "data"), s2
        print("OK")
    """)
    r = run_subprocess_py(code)
    assert "OK" in r.stdout, r.stderr


def test_rule_tables_consistent():
    for rules in (TRAIN_RULES, SERVE_RULES, LONG_CONTEXT_RULES):
        assert "embed" in rules.rules and "act_batch" in rules.rules
    assert SERVE_RULES.rules["cache_seq"] == "model"


# -- gradient compression -----------------------------------------------------
@given(st.integers(0, 5))
def test_int8_qdq_error_bounded(seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(0, 0.02, (1024,)).astype(np.float32))
    out = compression.compress_tree({"g": g})["g"]
    err = np.abs(np.asarray(out) - np.asarray(g))
    scale = np.abs(np.asarray(g)).max() / 127.0
    assert err.max() <= scale * 0.51 + 1e-9  # half-ulp of the block scale


def test_compress_tree_skips_tiny():
    g = jnp.ones((8,), jnp.float32)
    out = compression.compress_tree({"g": g})["g"]
    assert np.array_equal(np.asarray(out), np.asarray(g))


def test_compressed_psum_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.compression import compressed_psum
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = jnp.linspace(-1, 1, 512, dtype=jnp.float32)
        out = compressed_psum(x, mesh, "data")
        want = 4.0 * x
        err = float(jnp.max(jnp.abs(out - want)))
        assert err < 0.05, err
        print("OK")
    """)
    r = run_subprocess_py(code)
    assert "OK" in r.stdout, r.stderr


# -- pipeline parallelism ------------------------------------------------------
def test_pipeline_forward_matches_sequential_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_forward, bubble_fraction
        n_stages, layers_per, d = 4, 2, 16
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (n_stages, layers_per, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, d))  # 8 microbatches
        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        def layer_fn(wi, h):
            return jnp.tanh(h @ wi)
        got = pipeline_forward(layer_fn, w, x, mesh)
        # sequential reference
        ref = x
        for s in range(n_stages):
            for l in range(layers_per):
                ref = jnp.tanh(ref @ w[s, l])
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-5, err
        assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
        print("OK")
    """)
    r = run_subprocess_py(code)
    assert "OK" in r.stdout, r.stderr


# -- elastic -------------------------------------------------------------------
@given(st.integers(1, 64), st.sampled_from([4, 8, 16]))
def test_plan_shrink_properties(alive_groups, tp):
    n_alive = alive_groups * tp
    plan = plan_shrink(n_alive, model_parallel=tp, old_global_batch=256,
                       old_data=16)
    assert plan.data * plan.model <= n_alive
    assert plan.model == tp
    assert plan.global_batch % plan.data == 0


def test_plan_shrink_rejects_too_few():
    with pytest.raises(ValueError):
        plan_shrink(8, model_parallel=16, old_global_batch=256, old_data=16)


def test_elastic_resume_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_arch, smoke_config
        from repro.models import params as pmod, transformer
        from repro.models.steps import make_train_step
        from repro.optim import adamw
        from repro.parallel.axes import TRAIN_RULES, mesh_context
        from repro.runtime.elastic import make_elastic_mesh, plan_shrink, reshard_for

        cfg = smoke_config(get_arch("rsc-llm"))
        defs = transformer.model_defs(cfg)
        params = pmod.materialize(defs, seed=0)
        # start on 4x2, lose a "node", shrink to 2x2
        plan = plan_shrink(4, model_parallel=2, old_global_batch=8, old_data=4)
        mesh = make_elastic_mesh(plan)
        params2 = reshard_for(params, mesh, TRAIN_RULES, defs)
        step = make_train_step(cfg, adamw.AdamWConfig())
        batch = {"tokens": jnp.ones((plan.global_batch, 33), jnp.int32)}
        with mesh_context(mesh, TRAIN_RULES):
            with mesh:
                p, o, m = jax.jit(step)(params2, adamw.init(params2), batch)
        assert np.isfinite(float(m["loss"]))
        print("OK", plan.data, plan.model, plan.global_batch)
    """)
    r = run_subprocess_py(code)
    assert "OK" in r.stdout, r.stderr
