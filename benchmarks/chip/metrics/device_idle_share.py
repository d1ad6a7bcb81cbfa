"""Device layer: the share of the traced span the device sat idle.

1 minus the union of the TPU's program intervals over the traced span
(``devtrace.reduce``).
"""

from __future__ import annotations

def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace["idle_share"]
