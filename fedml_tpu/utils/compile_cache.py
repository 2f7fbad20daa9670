"""Where JAX's persistent compilation cache lives.

One rule, used by ``fedml_tpu.init()``, ``chip_smoke.py`` and the test
suite's ``conftest.py``: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
already read it and nothing is set in code; otherwise the cache is
``<checkout>/.jax_cache`` (git-ignored). The path is part of the cache
key, so it is never built from a temp directory, a pid or a time — a
directory that moves never hits.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory the rule above names (no side effects)."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Apply the rule before the first compile; returns the directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
