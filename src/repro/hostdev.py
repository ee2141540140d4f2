"""Process-level JAX setup that must run before the first compile.

``force_host_devices`` pins the XLA host-platform device count (stdlib
only, and it MUST run before jax initializes: the verify CLI, serving
harness and serve bench all need a multi-device host mesh on CPU).
``enable_compile_cache`` turns on JAX's persistent compilation cache so
a second run of a launcher reuses the first run's compiled programs.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count"

# <checkout>/.cache/jax: a fixed path, since the directory is part of
# what lets a later run find an entry (never a temp name, pid or time)
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")


def force_host_devices(n: int = 8) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS
    unless a count is already pinned there (an explicit operator setting
    wins)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG.lstrip("-") in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    stands untouched; otherwise the cache goes to ``JAX_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR
