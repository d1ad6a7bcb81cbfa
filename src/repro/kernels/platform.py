"""What the device path derives from the platform it runs on.

Three decisions live here and nowhere else:

* ``interpret_kernels()`` — the Pallas kernels are Mosaic (TPU) kernels:
  they compile on a TPU and run in the Pallas interpreter everywhere else
  (the CPU test suite), never the other way round;
* ``device_dtype(dtype)`` — the dtype an array crosses to the device in:
  64-bit counts become 32-bit on a TPU (which has no 64-bit matmul) and
  whenever jax's x64 mode is off; on a CPU under x64 they stay 64-bit, so
  the device backends stay bitwise comparable to the numpy oracle;
* ``enable_compile_cache()`` — the persistent compilation cache, turned on
  by entry points (never at import).

``CompileCounter`` counts the XLA executables a region builds, through
``jax.monitoring``; the served path's contract is that a warm pass over a
stream it has seen builds none.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
import numpy as np

__all__ = ["interpret_kernels", "device_dtype", "enable_compile_cache",
           "CompileCounter", "REPO_CACHE_DIR"]

# <repo>/.jax_cache: a fixed path (the cache key includes it), gitignored
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def interpret_kernels() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def device_dtype(dtype) -> np.dtype:
    """The dtype ``dtype`` takes on the device path (see module doc)."""
    dt = np.dtype(dtype)
    if dt.itemsize == 8 and (jax.default_backend() == "tpu"
                             or not jax.config.jax_enable_x64):
        return np.dtype(dt.kind + "4")
    return dt


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is; otherwise the
    cache goes to the fixed ``<repo>/.jax_cache``.
    Every program is cached, however short its compile: the served path
    builds many small kernels, each well under jax's default 1 s floor.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Context manager counting executables built inside it.

    ``compiles`` counts every backend compile request (a persistent-cache
    load included), ``cache_hits`` the loads among them, and ``seconds``
    their summed duration.
    """

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
