"""Where JAX's persistent compilation cache lives for the launchers.

A full-width train step takes tens of seconds to compile for a TPU, and
every fresh process pays it again unless the executable is found in the
persistent cache. The cache only hits when its directory stays put, so it
is never under a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache``, resolved from this package's own path
#: (``src/repro/launch/`` -> checkout root); listed in ``.gitignore``.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> "Path | None":
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting (read at
    import) and is left alone. Otherwise a TPU backend caches in
    :data:`CHECKOUT_CACHE_DIR`, and other backends cache nothing (returns
    None): their compiles are small, and XLA:CPU reports its own tuning
    flags as host mismatches each time it loads a cached executable.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return Path(jax.config.jax_compilation_cache_dir)
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return CHECKOUT_CACHE_DIR
