"""Plain query specs: what a query means, independent of the program.

A spec is a JSON-able dict::

    {"name": "q1",
     "pattern": ["seq", ["type", "Request"], ["kleene", ["type", "Travel"]],
                 ["not", ["type", "Pickup"]]],
     "aggs": [["count_star"], ["sum", "Travel", "duration"]],
     "preds": {"Request": [["rtype", "<", 5.0]]},
     "within": 30, "slide": 5, "group_by": ["district"]}

Aggregates are ``count_star``, ``count_type E``, ``sum E attr`` and
``avg E attr``.  ``analyze`` gives the automaton view the paper defines
(Sec. 3.1, Sec. 5): start and end types, predecessor edges, and the
negation constraints.  The reference (``reference.py``) evaluates specs
with it; ``program.py`` turns the same specs into the program's queries.
"""

from __future__ import annotations

from dataclasses import dataclass

AGG_KINDS = ("count_star", "count_type", "sum", "avg")


@dataclass(frozen=True)
class Neg:
    """NOT ``type`` between ``before`` and ``after`` (None: window edge)."""

    type: str
    before: frozenset | None
    after: frozenset | None


@dataclass(frozen=True)
class Info:
    start: frozenset
    end: frozenset
    edges: frozenset          # (predecessor type, successor type)
    types: frozenset          # positive types
    negatives: tuple = ()


def agg_name(agg) -> str:
    """The name a result carries for an aggregate (the program's form)."""
    kind = agg[0]
    if kind == "count_star":
        return "COUNT(*)"
    if kind == "count_type":
        return f"COUNT({agg[1]})"
    if kind in ("sum", "avg"):
        return f"{kind.upper()}({agg[1]}.{agg[2]})"
    raise ValueError(f"unknown aggregate {agg!r}")


def _positive(p) -> Info:
    op = p[0]
    if op == "type":
        s = frozenset({p[1]})
        return Info(s, s, frozenset(), s)
    if op == "kleene":
        inner = _positive(p[1])
        loop = frozenset((e, s) for e in inner.end for s in inner.start)
        return Info(inner.start, inner.end, inner.edges | loop, inner.types,
                    inner.negatives)
    if op != "seq":
        raise ValueError(f"{op!r} is not a pattern this reference evaluates")
    start = frontier = None
    edges, types, negs, pending = set(), set(), [], []
    for part in p[1:]:
        if part[0] == "not":
            if part[1][0] != "type":
                raise ValueError("NOT takes a single event type")
            pending.append(part[1][1])
            continue
        info = _positive(part)
        if info.types & types:
            raise ValueError("an event type appears twice in one pattern")
        if start is None:
            start = info.start
            negs += [Neg(t, None, info.start) for t in pending]
        else:
            edges |= {(a, b) for a in frontier for b in info.start}
            negs += [Neg(t, frontier, info.start) for t in pending]
        pending = []
        edges |= info.edges
        types |= info.types
        negs += info.negatives
        frontier = info.end
    if start is None:
        raise ValueError("SEQ needs a positive part")
    negs += [Neg(t, frontier, None) for t in pending]
    return Info(start, frontier, frozenset(edges), frozenset(types),
                tuple(negs))


def analyze(pattern) -> Info:
    info = _positive(pattern)
    if {n.type for n in info.negatives} & info.types:
        raise ValueError("a type is both positive and negative")
    return info


def validate(specs: list[dict], types, attrs) -> None:
    """Refuse what the reference does not evaluate."""
    for q in specs:
        info = analyze(q["pattern"])
        for t in info.types | {n.type for n in info.negatives}:
            if t not in types:
                raise ValueError(f"{q['name']}: unknown type {t!r}")
        for agg in q["aggs"]:
            if agg[0] not in AGG_KINDS:
                raise ValueError(f"{q['name']}: aggregate {agg[0]!r} is "
                                 "not one the reference evaluates")
            agg_name(agg)
        for t, ps in q["preds"].items():
            for attr, op, _ in ps:
                if attr not in attrs or op not in OPS:
                    raise ValueError(f"{q['name']}: bad predicate on {t}")
        if q["within"] <= 0 or q["slide"] <= 0:
            raise ValueError(f"{q['name']}: window and slide must be > 0")


OPS = {"<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
       ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
       "==": lambda a, b: a == b, "!=": lambda a, b: a != b}


def pane_ticks(specs: list[dict]) -> int:
    """gcd of every window and slide: the pane a window ends on."""
    from math import gcd

    g = 0
    for q in specs:
        g = gcd(gcd(g, q["within"]), q["slide"])
    return max(1, g)
