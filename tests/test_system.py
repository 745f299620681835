"""End-to-end behaviour: the paper's pipeline from cluster data to models
and mitigations, plus a small-mesh dry-run of the launch path."""
import json
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


def test_small_mesh_dryrun_subprocess():
    """The launch path (specs + shardings + lower + compile + analyses)
    works on a small forced mesh for a dense and a MoE arch."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, dataclasses
        from repro.configs.base import get_arch, smoke_config, ShapeSpec
        from repro.launch import specs, hlo_analysis
        from repro.launch.mesh import make_test_mesh
        from repro.models.steps import make_train_step, make_decode_step
        from repro.optim import adamw
        from repro.parallel.axes import mesh_context

        mesh = make_test_mesh(data=2, model=2, pod=2)
        for arch in ("qwen3-0.6b", "mixtral-8x22b"):
            cfg = smoke_config(get_arch(arch))
            shape = ShapeSpec("train_4k", "train", 64, 8)
            rules = specs.rules_for(shape)
            args = specs.input_specs(cfg, shape)
            in_sh = specs.input_shardings(cfg, shape, mesh, rules)
            out_sh = specs.output_shardings(cfg, shape, mesh, rules)
            fn = make_train_step(cfg, adamw.AdamWConfig())
            with mesh_context(mesh, rules):
                c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                            donate_argnums=(0, 1)).lower(*args).compile()
            mod = hlo_analysis.analyze_module(c.as_text(), pod_size=4)
            assert mod["flops"] > 0 and mod["bytes"] > 0
            assert mod["collectives"]["total_bytes"] > 0, arch
            # decode path
            shape_d = ShapeSpec("decode_32k", "decode", 128, 8)
            args_d = specs.input_specs(cfg, shape_d)
            in_d = specs.input_shardings(cfg, shape_d, mesh)
            out_d = specs.output_shardings(cfg, shape_d, mesh)
            fn_d = make_decode_step(cfg)
            with mesh_context(mesh, specs.rules_for(shape_d)):
                cd = jax.jit(fn_d, in_shardings=in_d, out_shardings=out_d,
                             donate_argnums=(1,)).lower(*args_d).compile()
            assert cd.memory_analysis().temp_size_in_bytes >= 0
            print("OK", arch)
    """)
    r = run_subprocess_py(code, timeout=900)
    assert r.stdout.count("OK") == 2, r.stderr[-3000:]


def test_dryrun_results_coverage(repo_root):
    """If the full 40-cell sweep has been run, every cell is accounted for."""
    import glob
    import os

    files = glob.glob(os.path.join(repo_root, "results", "dryrun", "*.json"))
    if len(files) < 80:
        pytest.skip("full dry-run sweep not present")
    recs = [json.load(open(f)) for f in files]
    assert len(recs) == 80
    assert all(r["status"] in ("ok", "skipped_full_attention") for r in recs)
    skips = [r for r in recs if r["status"] == "skipped_full_attention"]
    assert len(skips) == 10  # 5 archs x 2 meshes, long_500k only
    assert all(r["shape"] == "long_500k" for r in skips)
