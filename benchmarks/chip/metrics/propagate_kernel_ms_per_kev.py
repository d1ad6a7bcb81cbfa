"""Kernel layer: device time of the masked propagate kernel per 1,000
events consumed in the traced span.

The kernel's operations (``_propagate``) summed on the TPU's op line.
"""

from __future__ import annotations

def read(ctx):
    if ctx.trace is None or not ctx.traced_events:
        return None
    s = ctx.trace["kernel_s"]["propagate"]
    if s <= 0:
        return None
    return s * 1e3 / (ctx.traced_events / 1e3)
