"""JAX's persistent compilation cache at one fixed place per checkout."""
from __future__ import annotations

import os
import re
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (ignored by git).  The path is never built
    from a temporary name, a pid or the time: a cache that moves never
    hits.  Call it from a program's ``main()``, never at import — the test
    suite turns persistent-cache warnings into errors.

    A Pallas kernel's Mosaic body carries the source file of every op, and
    the cache key hashes that body, so the checkout root is stripped from
    source paths: a checkout at another path finds the same entries.
    """
    import jax
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
