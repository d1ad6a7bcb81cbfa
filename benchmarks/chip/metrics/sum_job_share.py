"""Execute layer: the share of propagate jobs that the sum round carries.

``batch_exec.jobs.sum`` over ``batch_exec.jobs.count`` +
``batch_exec.jobs.sum`` in the window: jobs submitted to the executor in
the second (SUM and AVG unit) round, of all jobs submitted in both rounds,
trivial ones (no launch) included.  Nothing to read from a program without
the counters, or in a window that submitted no job.
"""

from __future__ import annotations


def read(ctx):
    c = ctx.counters.get("batch_exec.jobs.count")
    s = ctx.counters.get("batch_exec.jobs.sum")
    if c is None or s is None or not c + s:
        return None
    return s / (c + s)
