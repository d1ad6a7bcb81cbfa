"""Execute layer: the sum-unit propagate round per 1,000 events.

``batch_exec.sum_round_s`` over the window (the micro-batch flush's
``execute.sum`` spans: building every SUM and AVG unit's injection rows
from the synced count results, its launches and its sync), divided by the
events the engine finished in it.  A checkout without the counter leaves
the metric out.
"""

from __future__ import annotations

from _counters import counters_ms_per_kev


def read(ctx):
    return counters_ms_per_kev(ctx, "batch_exec.sum_round_s")
