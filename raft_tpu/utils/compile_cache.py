"""Where the entry scripts keep JAX's persistent compilation cache.

Called by ``chip_smoke.py``, ``bench.py`` and the ``benchmarks/`` entry
scripts before their first compile — never at library import, so a
program that embeds raft_tpu keeps whatever cache policy it set.
"""

from __future__ import annotations

import os

#: the fixed cache directory at the repository root (git-ignored). A
#: fixed path matters: the path is part of the cache key, so a directory
#: that moves between runs never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is set here; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
