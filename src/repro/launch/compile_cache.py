"""Where JAX's persistent compilation cache lives, for every entry point.

``python -m repro.launch.serve``, ``python -m repro.launch.train``, the
scripts under ``examples/``, ``benchmarks/run.py`` and ``chip_smoke.py``
call :func:`setup_compile_cache` once, before their first compile.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here.
* Otherwise: ``<checkout>/.jax_cache``.  The directory is fixed (never a
  temp name, pid or time) so a later process on the same checkout finds
  the programs an earlier one compiled.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
