"""Plan layer: the share of planned graphlets stamped, not built.

``engine.plan.graphlets_stamped`` over ``engine.plan.graphlets`` in the
window: of the graphlet steps the planner made for the panes it planned
(plan-cache hits plan nothing), those copied from a look-alike member of
the same burst instead of built.  Nothing to read from a program without
the counters, or in a window that planned no graphlet.
"""

from __future__ import annotations


def read(ctx):
    n = ctx.counters.get("engine.plan.graphlets")
    stamped = ctx.counters.get("engine.plan.graphlets_stamped")
    if not n or stamped is None:
        return None
    return stamped / n
