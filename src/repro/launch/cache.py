"""JAX's persistent compilation cache at one fixed place per checkout.

A cache helps only where the next run looks for it, so without
``JAX_COMPILATION_CACHE_DIR`` it lives at ``<checkout>/.jax_cache``
(git-ignored). The entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` at the start of
``main``; importing this module sets nothing.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no directory; otherwise the cache goes to ``DEFAULT_DIR``.

    Either way the operations' metadata (their ``op_name`` with the step's
    phase scopes, ``repro.obs.telemetry.phase``) is part of the key. JAX
    strips it by default, and an executable cached from a program with
    other names, or none, would then load in place of this one, and the
    device trace would report the old names.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
