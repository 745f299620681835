"""The harness's refusals, and BENCHMARK.json against the files it names."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        harness.peaks_for(ROOT, "TPU v99 imaginary")
    v5e = harness.peaks_for(ROOT, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "sc2-3b.train-4k",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_non_zero_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_run_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_benchmark_names_files_that_exist(bench):
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert NAME.fullmatch(c["name"])
        with open(ROOT / c["file"]) as f:
            model = json.load(f)
        assert model["name"] == c["name"]
        assert model["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        _, _, model, traffic, limits = harness.cell(ROOT, w["name"])
        assert (ROOT / "chipbench" / "drivers"
                / f"{traffic['driver']}.py").is_file()
        assert limits and all("limit" in v for v in limits.values())
    ends = {e["name"] for e in bench["end_to_end"]}
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.fullmatch(e["name"]) and UNIT.fullmatch(e["unit"])
        assert e["better"] in ("lower", "higher")
    for e in bench["per_layer"]:
        assert e["moves"] in ends
        assert callable(harness.reader(ROOT, e["name"]))


def test_every_cell_reports_setup_an_end_to_end_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [e["name"] for e in bench["end_to_end"]
               if harness.applies(e, w["name"])]
        layer = [e for e in bench["per_layer"]
                 if harness.applies(e, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for e in layer:   # a layer metric moves a metric its cell reports
            assert e["moves"] in e2e
