"""The paper's ridesharing workload (Fig. 1 query shapes), n queries.

A copy of ``ridesharing_workload`` from ``repro.launch.hamlet_service``:
three Fig. 1 queries over requests and their travel trends per district,

* ``q1``  SEQ(Request, Travel+, NOT Pickup): COUNT(*), SUM(Travel.duration);
* ``q2``  SEQ(Request, Travel+, Dropoff) with ``rtype < 5`` on Request:
  COUNT(*), AVG(Travel.speed);
* ``q3``  SEQ(Request, Travel+, Cancel) with ``speed < 6`` on Travel:
  COUNT(*), SUM(Travel.duration), within 20;

then copies of them in turn, each with its own ``speed < 2 + (i mod 8)``
predicate on Travel in place of the original's.  Returns plain query specs
(see ``querylib``).
"""

from __future__ import annotations

R, T = ["type", "Request"], ["kleene", ["type", "Travel"]]


def build(n_queries: int, *, within: int = 30, slide: int = 5,
          short_within: int = 20) -> list[dict]:
    count = ["count_star"]
    base = [
        dict(pattern=["seq", R, T, ["not", ["type", "Pickup"]]],
             aggs=[count, ["sum", "Travel", "duration"]], preds={},
             within=within),
        dict(pattern=["seq", R, T, ["type", "Dropoff"]],
             aggs=[count, ["avg", "Travel", "speed"]],
             preds={"Request": [["rtype", "<", 5.0]]}, within=within),
        dict(pattern=["seq", R, T, ["type", "Cancel"]],
             aggs=[count, ["sum", "Travel", "duration"]],
             preds={"Travel": [["speed", "<", 6.0]]}, within=short_within),
    ]
    qs = []
    for i in range(n_queries):
        q = base[i % 3]
        preds = (q["preds"] if i < 3 else
                 {"Travel": [["speed", "<", 2.0 + ((i - 3) % 8)]]})
        qs.append(dict(q, name=f"q{i + 1}", preds=preds, slide=slide,
                       group_by=["district"]))
    return qs
