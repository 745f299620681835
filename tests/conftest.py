"""Test session config.

NOTE: tests intentionally see the single real CPU device — the 512-device
flag belongs exclusively to the dry-run (repro.launch.dryrun).  Tests that
need a multi-device mesh (pipeline, elastic, sharding) spawn subprocesses
with their own XLA_FLAGS.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from hypothesis import HealthCheck, settings  # noqa: E402

settings.register_profile(
    "repro",
    deadline=None,
    max_examples=25,
    suppress_health_check=[HealthCheck.too_slow],
)
settings.load_profile("repro")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def repo_root():
    return os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_subprocess_py(code: str, *, env_extra=None, timeout=600):
    """Run python code in a fresh process (own XLA flags)."""
    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-c", code], env=env, timeout=timeout,
        capture_output=True, text=True)
