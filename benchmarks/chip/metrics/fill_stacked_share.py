"""Finalize-and-fold layer: the share of divergent fold buckets whose
event-snapshot fills advanced every unit in one stacked step per row.

``fold_exec.fill.stacked`` over ``fold_exec.fill.stacked`` +
``fold_exec.fill.sequential`` in the window.  The counters appear with the
first bucket of their kind, so one of them alone counts the other as 0.
Nothing to read from a program without either counter, or in a window
that folded no divergent bucket.
"""

from __future__ import annotations


def read(ctx):
    s = ctx.counters.get("fold_exec.fill.stacked")
    q = ctx.counters.get("fold_exec.fill.sequential")
    if s is None and q is None:
        return None
    n = (s or 0) + (q or 0)
    return (s or 0) / n if n else None
