"""Execute layer: device launches per 1,000 events consumed.

The executor's launch counters (masked and dense propagate) over the
window.
"""

from __future__ import annotations

LAUNCHES = ("batch_exec.launches.masked", "batch_exec.launches.dense")


def read(ctx):
    if ctx.events <= 0:
        return None
    n = sum(ctx.counters.get(k, 0) for k in LAUNCHES)
    return n / (ctx.events / 1e3)
