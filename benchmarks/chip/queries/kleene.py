"""Paper workload 1: a shared Kleene sub-pattern under one window.

A copy of ``kleene_workload`` from ``benchmarks/common.py``: the queries
differ in their head type and, for every third query, a predicate on the
Kleene type.  Returns plain query specs (see ``querylib``).
"""

from __future__ import annotations


def build(n_queries: int, *, kleene_type: str, head_types: list[str],
          within: int = 60, slide: int = 30,
          pred_attr: str | None = None) -> list[dict]:
    qs = []
    for i in range(n_queries):
        preds = {}
        if pred_attr and i % 3 == 2:
            preds = {kleene_type: [[pred_attr, "<", 4.0 + (i % 5)]]}
        qs.append(dict(
            name=f"q{i}",
            pattern=["seq", ["type", head_types[i % len(head_types)]],
                     ["kleene", ["type", kleene_type]]],
            aggs=[["count_star"]], preds=preds, within=within, slide=slide,
            group_by=[]))
    return qs
