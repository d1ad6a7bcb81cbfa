"""Execute layer: the engine's execute time per 1,000 events consumed.

``RunStats.execute_s`` over the window: staging, kernel launches and the
sync per flush.
"""

from __future__ import annotations

from _common import per_kev


def read(ctx):
    return per_kev(ctx, "execute_s")
