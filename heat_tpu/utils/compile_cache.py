"""Placement of JAX's persistent compilation cache: one rule for every entry
point of this checkout (``chip_smoke.py``, ``tests/conftest.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no
directory is set in code; otherwise the cache is ``<checkout>/.jax_cache``
(git-ignored).  The path is part of what makes a run find its earlier
entries, so it is never ``/tmp``, a pid or a time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["configure"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure(min_compile_secs: float = 0.0) -> str:
    """Apply the rule and return the directory in effect.

    ``min_compile_secs`` is jax's threshold below which a compile is not
    written.  The default keeps everything: the eager ``ht.*`` path compiles
    one small program per ``(op, avals, split)`` (``core/_cache.py``), each
    well under a second, and a cold process pays for all of them again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    return path
