"""Ensemble engine gates: deterministic bands, executor contract, smokes.

The determinism contract is the load-bearing claim (ISSUE 4): aggregated
ensemble bands must be bit-identical regardless of worker count and of
the order cells complete in — otherwise "confidence band" figures would
not be reproducible across machines/core counts.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.ensemble.aggregate import BAND_METRICS, aggregate
from repro.ensemble.runner import (ReplayCell, default_min_gpus, grid,
                                   run_cells, run_replay_cell, scaled_spec)

QUICK_CELLS = grid([256, 512], range(2), horizon_days=1.0, min_hours=2.0)


@pytest.fixture(scope="module")
def quick_stats():
    """The 2-scale x 2-seed quick grid, run serially in-process."""
    return run_cells(run_replay_cell, QUICK_CELLS, procs=1)


# -- executor ---------------------------------------------------------------
def test_run_cells_serial_order_and_streaming(quick_stats):
    seen = []
    res = run_cells(lambda x: x * 10, [1, 2, 3],
                    procs=0, on_result=lambda i, r: seen.append((i, r)))
    assert res == [10, 20, 30]
    assert seen == [(0, 10), (1, 20), (2, 30)]


def _backend_probe(_task):
    """Pool worker: the stat backend it resolves and whether anything in it
    started a JAX backend."""
    from repro.core.backend import get_backend

    name = get_backend().name
    started = False
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        started = bool(xla_bridge._backends)
    return (name, os.environ.get("JAX_PLATFORMS"), started)


def test_pool_workers_never_start_a_jax_backend(monkeypatch):
    """One process per chip: a parent environment asking for the jax stat
    backend (and the chip) does not reach the spawn-pool workers, and the
    parent's environment is left as it was."""
    monkeypatch.setenv("REPRO_STAT_BACKEND", "jax_vmap")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    out = run_cells(_backend_probe, [0, 1], procs=2)
    assert out == [("NUMPY", "cpu", False)] * 2
    assert os.environ["REPRO_STAT_BACKEND"] == "jax_vmap"
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_run_cells_pool_matches_serial(quick_stats):
    """Spawn-pool results are per-cell identical to the serial run (modulo
    wall-clock) and arrive in task order in the returned list."""
    pooled = run_cells(run_replay_cell, QUICK_CELLS, procs=2)
    for a, b in zip(quick_stats, pooled):
        da, db = a.to_json(), b.to_json()
        da.pop("wall_s"), db.pop("wall_s")
        assert json.dumps(da, sort_keys=True) == json.dumps(db,
                                                            sort_keys=True)


# -- determinism ------------------------------------------------------------
def _bands_json(stats) -> str:
    agg = aggregate(stats)
    return json.dumps(agg.to_json()["scales"], sort_keys=True)


def test_bands_identical_any_completion_order(quick_stats):
    ref = _bands_json(quick_stats)
    rng = np.random.default_rng(0)
    for _ in range(4):
        shuffled = list(quick_stats)
        rng.shuffle(shuffled)
        assert _bands_json(shuffled) == ref


def test_bands_identical_across_worker_counts(quick_stats):
    pooled = run_cells(run_replay_cell, QUICK_CELLS, procs=2)
    assert _bands_json(pooled) == _bands_json(quick_stats)


def test_aggregator_rejects_duplicate_cells(quick_stats):
    agg = aggregate(quick_stats)
    with pytest.raises(ValueError, match="duplicate"):
        agg.add(quick_stats[0])


# -- cell scoring -----------------------------------------------------------
def test_cell_stats_sane(quick_stats):
    for c in quick_stats:
        assert c.n_records > 50
        assert c.n_faults >= 0
        assert 0.0 < c.goodput <= 1.0
        assert c.sim_days == 1.0
        assert sum(c.attribution.values()) == pytest.approx(1.0) \
            or not c.attribution


def test_band_shape_and_percentile_order(quick_stats):
    agg = aggregate(quick_stats)
    assert agg.scales() == [256, 512]
    for g in agg.scales():
        bands = agg.bands(g)
        assert set(bands) == set(BAND_METRICS)
        b = bands["goodput"]
        assert b.n == 2
        assert b.lo <= b.p5 <= b.p25 <= b.p50 <= b.p75 <= b.p95 <= b.hi
        assert b.lo <= b.mean <= b.hi


def test_score_cell_matches_sweep_scorer():
    """The sweep's per-cell metrics and the ensemble's come from the same
    scorer: a baseline sweep cell equals a bare ensemble cell at the same
    (scale, seed, horizon)."""
    from repro.mitigations.sweep import run_cell

    cell = run_replay_cell(ReplayCell(n_gpus=512, seed=1, horizon_days=1.5,
                                      min_hours=2.0))
    sweep_cell = run_cell("baseline", 512, 1, horizon_days=1.5,
                          min_hours=2.0)
    for f in ("n_records", "n_faults", "n_infra_failures",
              "n_runs_measured", "mttf_large_h", "goodput"):
        a, b = getattr(cell, f), getattr(sweep_cell, f)
        assert a == pytest.approx(b, nan_ok=True), f
    assert cell.ettr_sim == pytest.approx(sweep_cell.ettr_sim, nan_ok=True)


def test_scaled_spec_and_min_gpus():
    spec = scaled_spec(1024)
    assert spec.n_nodes == 128
    assert spec.max_job_gpus == 1024
    assert spec.jobs_per_day == pytest.approx(128 * 3.6)
    assert default_min_gpus(1024) == 64
    assert default_min_gpus(16384) == 1024


# -- CLI / benchmark smokes --------------------------------------------------
def _subproc(repo_root, args, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo_root, "src")
    return subprocess.run([sys.executable, *args], cwd=repo_root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_ensemble_cli_smoke(repo_root, tmp_path):
    out = tmp_path / "ens.json"
    proc = _subproc(repo_root, [
        "-m", "repro.ensemble.run", "--gpus", "256,512", "--seeds", "2",
        "--days", "1", "--min-hours", "2", "--procs", "2",
        "--json", str(out)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cells in" in proc.stdout
    data = json.loads(out.read_text())
    assert data["n_cells"] == 4
    assert set(data["scales"]) == {"256", "512"}
    for scale in data["scales"].values():
        assert set(scale["bands"]) == set(BAND_METRICS)


def test_ensemble_bench_quick_smoke(repo_root):
    """Tier-1 guard: `benchmarks.run --only ensemble_bench --quick` must
    run end-to-end with the determinism check passing."""
    proc = _subproc(repo_root, ["-m", "benchmarks.run", "--only",
                                "ensemble_bench", "--quick"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ensemble_bench" in proc.stdout
    assert "[PASS] bands bit-identical across worker counts" in proc.stdout


def test_fig11_quick_smoke(repo_root):
    """Tier-1 guard: `benchmarks.run --only fig11_scale_projection --quick`
    runs the ensemble -> fit -> projection pipeline end-to-end."""
    proc = _subproc(repo_root, ["-m", "benchmarks.run", "--only",
                                "fig11_scale_projection", "--quick"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "fig11_scale_projection" in proc.stdout
    assert "projection_16384gpu_h" in proc.stdout
