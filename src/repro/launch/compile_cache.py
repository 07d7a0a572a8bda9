"""Where this repository's entry points keep JAX's persistent compile cache.

The cache key includes the directory, so the path is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache`` -- never a name built from a temporary
directory, a process id or the time.
"""

from __future__ import annotations

import os


def enable_compilation_cache(checkout: str) -> str:
    """Turn the persistent compilation cache on for this process and return
    its directory.  Call it from an entry point, before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
