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

One pair of ``jax.monitoring`` listeners, registered once per process
(``watch_compiles()``), sees every XLA executable the process builds or
loads from the persistent cache.  ``CompileCounter`` counts the executables
a region builds through it (the served path's contract is that a warm pass
over a stream it has seen builds none); ``thread_compile_seconds()`` is the
compile time of the calling thread, which the overload controller takes
out of its pane latency; ``add_compile_sink()`` hands each build to an
observability layer with tracing on.
"""

from __future__ import annotations

import os
import threading
import weakref
from pathlib import Path

import jax
import numpy as np

__all__ = ["interpret_kernels", "device_dtype", "enable_compile_cache",
           "CompileCounter", "REPO_CACHE_DIR", "watch_compiles",
           "thread_compile_seconds", "add_compile_sink"]

# <repo>/.jax_cache: a fixed path (the cache key includes it), gitignored
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# a program's first use: tracing to a jaxpr, lowering, the backend compile
_FIRST_USE = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              _BACKEND_COMPILE)
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


class _Compiles:
    """Totals of the one listener pair: process-wide, as ``jax.monitoring``
    listeners are."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.local = threading.local()     # per-thread ``seconds``
        self.sinks: tuple = ()             # weak refs to ``on_compile``s
        self.lock = threading.Lock()
        self.watching = False


_COMPILES = _Compiles()


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event not in _FIRST_USE:
        return
    c = _COMPILES
    c.local.seconds = getattr(c.local, "seconds", 0.0) + secs
    if event != _BACKEND_COMPILE:
        return
    with c.lock:          # threads may compile at once; builds are rare
        c.compiles += 1
        c.seconds += secs
        for ref in c.sinks:
            sink = ref()
            if sink is not None:
                sink(secs)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        with _COMPILES.lock:
            _COMPILES.cache_hits += 1


def watch_compiles() -> None:
    """Register the listener pair, once per process (idempotent)."""
    c = _COMPILES
    with c.lock:
        if not c.watching:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            c.watching = True


def thread_compile_seconds() -> float:
    """Seconds the calling thread has spent on programs' first uses since
    ``watch_compiles()``: tracing, lowering, and building or loading the
    executable (the listener fires on the compiling thread)."""
    return getattr(_COMPILES.local, "seconds", 0.0)


def add_compile_sink(method) -> None:
    """Call the bound ``method(secs)`` on the compiling thread for every
    executable built or loaded; held weakly, so the sink goes with its
    owner."""
    watch_compiles()
    c = _COMPILES
    with c.lock:
        c.sinks = tuple(r for r in c.sinks if r() is not None) + (
            weakref.WeakMethod(method),)


class CompileCounter:
    """Context manager counting executables built inside it.

    ``compiles`` counts every backend compile request (a persistent-cache
    load included), ``cache_hits`` the loads among them, and ``seconds``
    their summed duration: the process-wide listener's totals over the
    region, from any thread.
    """

    def __init__(self):
        self._start = self._end = None

    @staticmethod
    def _now() -> tuple:
        c = _COMPILES
        return (c.compiles, c.cache_hits, c.seconds)

    def _delta(self, i: int):
        if self._start is None:
            return 0
        end = self._end if self._end is not None else self._now()
        return end[i] - self._start[i]

    @property
    def compiles(self) -> int:
        return self._delta(0)

    @property
    def cache_hits(self) -> int:
        return self._delta(1)

    @property
    def seconds(self) -> float:
        return float(self._delta(2))

    def __enter__(self) -> "CompileCounter":
        watch_compiles()
        self._start, self._end = self._now(), None
        return self

    def __exit__(self, *exc) -> None:
        self._end = self._now()
