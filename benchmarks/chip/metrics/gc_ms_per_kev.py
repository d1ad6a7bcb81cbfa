"""Host runtime (GC) layer: collector pauses per 1,000 events.

``host.gc_s`` over the window (every collection, from a ``gc.callbacks``
hook, on whichever thread it stops), divided by the events the engine
finished in it.  It nests inside the pump's other spans.
"""

from __future__ import annotations

from _counters import counters_ms_per_kev


def read(ctx):
    return counters_ms_per_kev(ctx, "host.gc_s")
