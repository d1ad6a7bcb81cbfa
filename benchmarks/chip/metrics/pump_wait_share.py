"""Serve layer: the share of the window the pump waited for sealed input.

``serve.wait_s`` over the window (the front-end's ``serve.wait`` spans:
runs of pump cycles that sealed and routed nothing, with the sleeps
between them) divided by the window's seconds.
"""

from __future__ import annotations


def read(ctx):
    wait = ctx.counters.get("serve.wait_s")
    if wait is None or ctx.events <= 0:
        return None
    return wait / ctx.window_s
