"""Finalize-and-fold layer: fold time per 1,000 events consumed.

``RunStats.fold_s`` over the window.
"""

from __future__ import annotations

from _common import per_kev


def read(ctx):
    return per_kev(ctx, "fold_s")
