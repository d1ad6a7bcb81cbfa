"""Plan layer: the engine's plan time per 1,000 events consumed.

``RunStats.plan_s`` over the window (the engine's own timer), divided by
the events the engine consumed in it.
"""

from __future__ import annotations

from _common import per_kev


def read(ctx):
    return per_kev(ctx, "plan_s")
