"""Where the persistent XLA compilation cache lives.

Every entry point (``launch/serve.py``, ``chip_smoke.py``,
``benchmarks/run.py``, the examples) calls :func:`use_compile_cache` once
from its ``main``; importing this module sets nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  points the cache nowhere else.
* unset: the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed (no temporary name, process id or
  timestamp) so that the next run of the same checkout finds it again.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
