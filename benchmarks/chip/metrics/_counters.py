"""Shared arithmetic of the readers of the program's time counters.

The counters are registered when the program's observability is built with
tracing on, so a traced run of a program that has them reads 0 for a
window without the work; a program without them leaves the metric out.
"""

from __future__ import annotations


def counters_ms_per_kev(ctx, *names: str):
    """Milliseconds summed over the counters ``names`` (seconds) per 1,000
    events finished in the window."""
    c = ctx.counters
    if any(n not in c for n in names) or ctx.events <= 0:
        return None
    return sum(c[n] for n in names) * 1e3 / (ctx.events / 1e3)
