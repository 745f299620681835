"""chip_smoke.py rehearsed on the CPU: smoke widths and interpret-mode
kernels (``REHEARSAL``, which the script refuses on a chip) drive every
phase, check and the result line; at full size it refuses to run without a
chip, and outside a checkout."""
import json
import os
import shutil
import subprocess
import sys

from tests.conftest import run_subprocess_py


def _rehearse(repo_root, argv, env_extra=None):
    code = ("import chip_smoke; "
            f"chip_smoke.main({argv!r}, chip_smoke.REHEARSAL)")
    r = run_subprocess_py(code, env_extra={"PYTHONPATH": repo_root,
                                           **(env_extra or {})}, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout.strip().splitlines()


def test_rehearsal_one_chip_phases(repo_root):
    lines = _rehearse(repo_root, [])
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    text = "\n".join(lines)
    for phase in ("train:", "serve:", "bands:", "kernels:"):
        assert phase in text


def test_rehearsal_four_chips(repo_root):
    lines = _rehearse(repo_root, ["--chips", "4"], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert json.loads(lines[-1])["device"]["count"] == 4
    assert sum("vs one chip" in ln for ln in lines) == 2


def _run_script(path, cwd):
    return subprocess.run([sys.executable, str(path)], cwd=cwd, timeout=300,
                          capture_output=True, text=True)


def test_refuses_without_chip(repo_root):
    r = _run_script(os.path.join(repo_root, "chip_smoke.py"), repo_root)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_refuses_outside_a_checkout(repo_root, tmp_path):
    shutil.copy(os.path.join(repo_root, "chip_smoke.py"), tmp_path)
    r = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
