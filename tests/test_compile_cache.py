"""The persistent compilation cache lives in exactly one place: the
directory ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
Each case runs in a fresh process (the cache is process-global jax state)."""
import json
import os
import textwrap

import pytest

from tests.conftest import run_subprocess_py

PROBE = textwrap.dedent("""
    import json, pathlib, sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import DEFAULT_DIR, configure_compile_cache

    def files(d):
        d = pathlib.Path(d)
        return {str(p) for p in d.rglob("*")} if d.exists() else set()

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    default_before = files(DEFAULT_DIR)
    d = configure_compile_cache()
    before = files(d)
    salt = float(sys.argv[1])
    jax.jit(lambda x: jnp.sin(x) * salt)(jnp.arange(17.0)).block_until_ready()
    print(json.dumps({
        "dir": d, "config": jax.config.jax_compilation_cache_dir,
        "default": str(DEFAULT_DIR),
        "new_here": len(files(d) - before),
        "new_default": len(files(DEFAULT_DIR) - default_before)}))
""")


def _probe(env_extra, salt):
    r = run_subprocess_py(PROBE.replace("sys.argv[1]", repr(str(salt))),
                          env_extra=env_extra, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placement(tmp_path, repo_root, env_set):
    salt = 1.0 + (os.getpid() % 1000) / 1000.0  # a program not yet cached
    if env_set:
        want = str(tmp_path / "cc")
        out = _probe({"JAX_COMPILATION_CACHE_DIR": want}, salt)
        assert out["dir"] == out["config"] == want
        assert out["new_here"] > 0
        assert out["new_default"] == 0   # nothing lands anywhere else
    else:
        env = {"JAX_COMPILATION_CACHE_DIR": ""}
        out = _probe(env, salt)
        want = os.path.join(repo_root, ".jax_cache")
        assert out["dir"] == out["config"] == out["default"] == want
        assert out["new_here"] > 0
