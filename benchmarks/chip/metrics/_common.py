"""Shared arithmetic of the per-layer readers."""

from __future__ import annotations


def per_kev(ctx, stat: str):
    """Milliseconds of an engine timer per 1,000 events consumed, over the
    window."""
    if ctx.events <= 0:
        return None
    return ctx.stats[stat] * 1e3 / (ctx.events / 1e3)
