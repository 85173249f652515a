"""Persistent compilation cache for the program's entry points.

Entry points call `enable_compile_cache` before their first compile;
importing the library never touches the cache.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
else is configured here; otherwise the cache lives at a fixed path
inside the checkout (`<repo>/.jax_cache`, gitignored).  The path is
part of the cache key, so it is never a temp, pid- or time-based one.
"""
from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> Optional[str]:
    """The checkout this package runs from (`<root>/src/repro/...` with
    a `pyproject.toml` at `<root>`), or None for an installed copy."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.dirname(os.path.dirname(here))
    root = os.path.dirname(src)
    if (os.path.basename(src) == "src"
            and os.path.isfile(os.path.join(root, "pyproject.toml"))):
        return root
    return None


def enable_compile_cache(repo_root: Optional[str]) -> Optional[str]:
    """Turn the persistent cache on; returns the directory in use.

    With no `repo_root` (an installed copy, outside any checkout) and
    no `JAX_COMPILATION_CACHE_DIR`, nothing is cached: there is no
    checkout to keep the cache in, and a shared location would mix
    checkouts."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        if repo_root is None:
            return None
        path = os.path.join(os.path.abspath(repo_root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, kernels included: a restart recompiles
    # nothing it has compiled before
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
