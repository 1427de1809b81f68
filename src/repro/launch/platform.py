"""The device the served kernels run on, and JAX's persistent compile cache.

This is the one place that decides whether Pallas kernels run compiled or in
interpret mode: interpret mode exists only because the CPU backend has no
Pallas compiler, so it follows from the platform and is never a user option.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Where the compile cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: A fixed path inside the checkout, git-ignored: the path is part of what a
#: later process must find again, so it holds no tempdir, pid or timestamp.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def interpret() -> bool:
    """True only on the CPU backend, where Pallas kernels are interpreted."""
    return jax.default_backend() == "cpu"


def device_info() -> dict:
    """The serving device as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache for every program; return its dir.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` by itself, so a directory
    (:data:`DEFAULT_CACHE_DIR`) is set here only when the variable is
    absent. The thresholds drop to zero because the served kernels compile
    in well under JAX's default 1 s minimum and would otherwise never be
    cached. Call it from an entry point before the first compile, never at
    import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
