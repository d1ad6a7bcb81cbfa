"""Finalize-and-fold layer: finalize time per 1,000 events consumed.

``RunStats.finalize_s`` over the window.
"""

from __future__ import annotations

from _common import per_kev


def read(ctx):
    return per_kev(ctx, "finalize_s")
