"""End-to-end behaviour: the paper's pipeline from cluster data to models
and mitigations, plus the train and decode steps compiled on a small
mesh."""
import textwrap

import numpy as np
import pytest

from tests.conftest import run_subprocess_py
from repro.cluster import analysis
from repro.cluster.scheduler import ClusterSim
from repro.cluster.workload import ClusterSpec
from repro.core import mttf_model
from repro.core.ettr_model import ETTRParams, expected_ettr


@pytest.fixture(scope="module")
def sim():
    spec = ClusterSpec("RSC-1", n_nodes=300, jobs_per_day=1100,
                       target_utilization=0.8, r_f=6.5e-3)
    s = ClusterSim(spec, horizon_days=6.0, seed=5)
    s.run()
    return s


def test_sim_to_mttf_model_to_projection(sim):
    """Full loop: simulate -> fit r_f -> project -> compare to analytic."""
    rf = mttf_model.fit_r_f(sim.records, min_gpus=64,
                            require_hw_attribution=False)
    assert np.isfinite(rf) and rf > 0
    proj = mttf_model.projection_table(rf)
    # doubling GPUs halves MTTF
    assert proj[2048] == pytest.approx(proj[1024] / 2, rel=1e-6)
    assert proj[16384] < proj[1024]


def test_sim_ettr_vs_analytic(sim):
    """Measured job-run ETTRs bracket the analytical expectation."""
    rf = max(mttf_model.fit_r_f(sim.records, min_gpus=64,
                                require_hw_attribution=False), 1e-4)
    rows = analysis.run_ettrs(sim.records, min_gpus=128, min_hours=24.0,
                              r_f_per_node_day=rf)
    if len(rows) >= 3:
        measured = np.mean([r.ettr for _, r in rows])
        expect = expected_ettr(ETTRParams(
            n_nodes=256 // 8, r_f=rf, w_cp_s=300, u0_s=300,
            runtime_s=48 * 3600.0))
        assert abs(measured - expect) < 0.35


def test_goodput_loss_split(sim):
    casc = analysis.preemption_cascades(sim.records)
    assert casc["failure_loss_gpu_h"] > 0
    assert 0.0 <= casc["second_order_fraction"] < 0.8


def test_attribution_mix_dominated_by_fig4_modes(sim):
    rates = analysis.attribution_rates(
        sim.records, sim.fault_log, sim.spec.n_gpus, sim.horizon_s)
    if rates:
        top = set(list(rates)[:4])
        assert top & {"ib_link_error", "filesystem_mount",
                      "gpu_memory_errors", "pcie_errors", "gpu_unavailable"}


def test_small_mesh_steps_compile_subprocess():
    """The train step and the decode step of a dense and a MoE arch compile
    on a small (pod, data, model) mesh, parameters and optimizer state
    placed by ``reshard_for`` under TRAIN_RULES as the four-chip path places
    them; the train step's program holds a cross-device collective."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import re
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_arch, smoke_config
        from repro.launch.mesh import make_test_mesh
        from repro.models import params as pmod
        from repro.models import transformer
        from repro.models.steps import (make_decode_step, make_prefill_step,
                                        make_train_step)
        from repro.optim import adamw
        from repro.parallel.axes import TRAIN_RULES, mesh_context
        from repro.runtime.elastic import reshard_for

        mesh = make_test_mesh(data=2, model=2, pod=2)
        rep = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P(("pod", "data")))
        for arch in ("qwen3-0.6b", "mixtral-8x22b"):
            cfg = smoke_config(get_arch(arch))
            defs = transformer.model_defs(cfg)
            params = pmod.materialize(defs, seed=0)
            opt = adamw.init(params)
            placed = (reshard_for(params, mesh, TRAIN_RULES, defs),
                      adamw.AdamWState(
                          jax.device_put(opt.step, rep),
                          reshard_for(opt.m, mesh, TRAIN_RULES, defs),
                          reshard_for(opt.v, mesh, TRAIN_RULES, defs)))
            batch = {"tokens": jax.device_put(
                jnp.zeros((8, 65), jnp.int32), rows)}
            step = make_train_step(cfg, adamw.AdamWConfig())
            with mesh_context(mesh, TRAIN_RULES):
                c = jax.jit(step, donate_argnums=(0, 1)).lower(
                    *placed, batch).compile()
            assert re.search(r"all-reduce|all-gather|reduce-scatter|"
                             r"all-to-all|collective-permute",
                             c.as_text()), arch
            # decode, against a cache sized as prefill leaves it
            sdefs = pmod.cast_defs(defs, jnp.bfloat16)
            sparams = reshard_for(pmod.materialize(sdefs, seed=0), mesh,
                                  TRAIN_RULES, sdefs)
            tokens = jax.device_put(jnp.zeros((8, 1), jnp.int32), rows)
            with mesh_context(mesh, TRAIN_RULES):
                cache = jax.eval_shape(make_prefill_step(cfg, cache_len=128),
                                       sparams, {"tokens": jnp.zeros(
                                           (8, 120), jnp.int32)})[1]
                cd = jax.jit(make_decode_step(cfg), donate_argnums=(1,)).lower(
                    sparams, cache, tokens).compile()
            assert cd.memory_analysis().temp_size_in_bytes >= 0
            print("OK", arch)
    """)
    r = run_subprocess_py(code, timeout=900)
    assert r.stdout.count("OK") == 2, r.stderr[-3000:]
