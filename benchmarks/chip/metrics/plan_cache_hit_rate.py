"""Plan layer: the share of pane plans served from the plan cache.

``plan_cache_hits / (hits + misses)`` over the window; nothing to read
when the window planned nothing.
"""

from __future__ import annotations

def read(ctx):
    h = ctx.stats["plan_cache_hits"]
    n = h + ctx.stats["plan_cache_misses"]
    return h / n if n else None
