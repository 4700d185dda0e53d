"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so a path that moves (a temp
dir, a per-pid or time-stamped name) never hits.  An operator places
the cache with ``JAX_COMPILATION_CACHE_DIR``; JAX reads that variable
itself, so then nothing is set here.  Otherwise the cache lives at the
fixed ``<repo>/.jax_cache`` (listed in .gitignore).

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``bench.py``, ``cilium-tpu daemon run``); importing the library never
turns the cache on, and the tests keep it off (tests/conftest.py).
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def compile_cache_dir(env: Optional[Mapping[str, str]] = None) -> str:
    """The directory the cache uses: the variable when set, else the
    fixed in-repo default."""
    env = os.environ if env is None else env
    return env.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
