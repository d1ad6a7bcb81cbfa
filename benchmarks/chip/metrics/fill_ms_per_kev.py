"""Finalize-and-fold layer: the event-snapshot fills per 1,000 events.

``fold_exec.fill_s`` over the window (the ``finalize.fill`` spans, one per
divergent fold bucket: the bucket's snapshot-fill set-up and its loop over
the divergent rows), divided by the events the engine finished in it.  A
checkout without the counter leaves the metric out.
"""

from __future__ import annotations

from _counters import counters_ms_per_kev


def read(ctx):
    return counters_ms_per_kev(ctx, "fold_exec.fill_s")
