"""Where the entry points keep what they compile.

Both caches live at fixed paths inside the checkout: JAX keys its
persistent compilation cache partly by the cache path, so a directory
that moves with the working directory never hits.

* :func:`enable_compile_cache` — JAX's persistent compilation cache.
  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing here overrides it; otherwise ``<checkout>/.jax_cache``.  Only
  entry points call it (never at import, never in tests).
* :func:`checkout_path` — anchors default paths such as the serve
  launcher's AOT program cache (``artifacts/progcache``) to the checkout
  instead of the working directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["checkout_path", "enable_compile_cache"]

#: the repository checkout this package was imported from
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def checkout_path(*parts: str) -> str:
    return str(_CHECKOUT.joinpath(*parts))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = checkout_path(".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
