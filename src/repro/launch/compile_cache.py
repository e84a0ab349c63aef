"""Where JAX keeps its persistent compilation cache.

HiFT compiles one jitted step per group, so cold compiles are a large share
of a short run.  The entry points (``launch.train``, ``launch.serve``,
``chip_smoke.py``) call :func:`setup_compile_cache` once at startup, before
the first compile.  The cache key includes its directory, so the directory
is fixed: a path built from a temp name, a pid or a time would never hit.

The key also includes each program's metadata (its name scopes and source
lines).  Without it an executable loaded from the cache keeps the names of
the source that first compiled it, and a profile of a changed program
shows the old names: the benchmark reads the ``update`` scope and the
backward's ``transpose(...)`` from those names.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache (gitignored); this file is <repo>/src/repro/launch/
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    no directory is set here.  Otherwise the cache goes to
    :data:`DEFAULT_DIR` inside the checkout.  Either way entries are keyed
    on the programs' metadata too."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
