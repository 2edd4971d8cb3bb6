"""Where JAX keeps compiled programs between processes.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the persistent cache lives in one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): a cache only hits when later runs look in the same place,
so the path never depends on a temp name, a pid or the time. The TPU
compile of the float64 fit takes minutes, which makes this the difference
between a usable cold run and a second cold run.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "configure"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
