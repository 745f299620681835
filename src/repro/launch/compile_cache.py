"""Where JAX keeps its persistent compilation cache: decided here only.

Every entry point that compiles for the chip (``repro.launch.train``,
``repro.launch.serve``, ``benchmarks.run``, ``chip_smoke.py``) calls
:func:`configure_compile_cache` once, before it compiles anything.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set.
* Otherwise: ``<checkout>/.jax_cache`` (git-ignored).  The path is fixed —
  never a temp name, pid or time — because a cache that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
