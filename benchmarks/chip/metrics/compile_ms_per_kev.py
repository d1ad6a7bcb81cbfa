"""Compile (host) layer: programs built or loaded per 1,000 events.

``kernels.compile_s`` over the window (the seconds of every executable
built, or loaded from the persistent cache, as JAX's
``backend_compile_duration`` event reports them), divided by the events
the engine finished in it.  It nests inside the pump's other spans.
"""

from __future__ import annotations

from _counters import counters_ms_per_kev


def read(ctx):
    return counters_ms_per_kev(ctx, "kernels.compile_s")
