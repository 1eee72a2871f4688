"""Persistent XLA compilation cache for the entry points.

Called first thing by the entry points (`launch/train.py:main`,
`chip_smoke.py`), never by a library module on import. The cache lives
where `JAX_COMPILATION_CACHE_DIR` says when it is set (JAX reads the
variable itself, and nothing here overrides it). Otherwise it lives at the
fixed path `<checkout>/.jax_compile_cache`: the directory is part of what a
later run must find again, so it never depends on a temp name, a pid or
the time.

A process pinned to the CPU (`JAX_PLATFORMS=cpu`: the test suite and the
multi-process CPU runtime) keeps no cache unless the variable asks for
one. Its compiles are small, and concurrent test workers would otherwise
share one directory."""
from __future__ import annotations

import os

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_compile_cache")


def enable_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its directory and
    return that directory (None when this process keeps no cache)."""
    if os.environ.get(ENV_DIR):
        return os.environ[ENV_DIR]
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
