"""Finalize and fold layer: window-result construction per 1,000 events.

``engine.emit_s`` over the window (the engine's ``emit`` spans, one per
pane: the results of the windows the pane closes and the retiring of their
instances, outside ``advance_instances``), divided by the events the
engine finished in it.
"""

from __future__ import annotations

from _counters import counters_ms_per_kev


def read(ctx):
    return counters_ms_per_kev(ctx, "engine.emit_s")
