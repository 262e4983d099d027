"""Where JAX's persistent compilation cache lives — one rule, one place.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
code sets nothing: whoever runs the program placed the cache. Otherwise
the cache goes to ``<checkout>/.jax_compile_cache`` (gitignored) — a
fixed path, because the path is part of the cache key and a directory
that moves never hits.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir != CHECKOUT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
