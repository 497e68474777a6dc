"""Where compiled programs persist between runs.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory: JAX
reads the variable itself, so nothing is set in code.  Otherwise the cache
sits at one fixed path inside the checkout, ``<repo>/.jax_cache``
(git-ignored), so a second run of the same program on the same machine
finds the first run's executables.
"""
from __future__ import annotations

import os

import jax

#: The checkout's cache directory, used when the environment names none.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
