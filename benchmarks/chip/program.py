"""The system under test, as the benchmark reaches it.

Everything the benchmark takes from the program goes through here: the
query and event types the program consumes, and the served path
(``ServingServer`` over ``ServingFrontend``).  The yardstick modules
(generators, query builders, reference, comparison, trace reduction) import
nothing of the program.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def import_program():
    """Put the checkout's ``src`` on the path and import the package; a
    directory without it is refused here."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (ImportError names what is missing)


def schema(types, attrs):
    from repro.core.events import StreamSchema

    return StreamSchema(types=tuple(types), attrs=tuple(attrs))


def _pattern(p):
    from repro.core.pattern import EventType, Kleene, Not, Seq

    op = p[0]
    if op == "type":
        return EventType(p[1])
    if op == "kleene":
        return Kleene(_pattern(p[1]))
    if op == "not":
        return Not(_pattern(p[1]))
    if op == "seq":
        return Seq(*(_pattern(x) for x in p[1:]))
    raise ValueError(f"unknown pattern op {op!r}")


def _agg(a):
    from repro.core import query as Q

    kind = a[0]
    if kind == "count_star":
        return Q.count_star()
    if kind == "count_type":
        return Q.count_type(a[1])
    if kind == "sum":
        return Q.agg_sum(a[1], a[2])
    if kind == "avg":
        return Q.agg_avg(a[1], a[2])
    raise ValueError(f"unknown aggregate {kind!r}")


def workload(specs: list[dict], types, attrs):
    """The program's ``Workload`` for plain query specs."""
    from repro.core.query import Pred, Query, Workload

    qs = []
    for q in specs:
        preds = ({t: [Pred(a, op, float(v)) for a, op, v in ps]
                  for t, ps in q["preds"].items()} or None)
        qs.append(Query(q["name"], _pattern(q["pattern"]),
                        aggs=tuple(_agg(a) for a in q["aggs"]), preds=preds,
                        within=q["within"], slide=q["slide"],
                        group_by=tuple(q["group_by"])))
    return Workload(schema(types, attrs), qs)


def event_batch(sch, arrays: dict):
    from repro.core.events import EventBatch

    return EventBatch(sch, arrays["type_id"], arrays["time"],
                      arrays["attrs"], arrays["group"],
                      seq=arrays.get("seq"))


def serve(wl, serve_cfg: dict, groups_per_tenant: int, obs):
    """Start the served path: ``(server, frontend, host, port)``."""
    from repro.overload import OverloadConfig
    from repro.serve import ServingFrontend, ServingServer

    fe = ServingFrontend(
        wl, backend=serve_cfg["backend"],
        overload=OverloadConfig(**serve_cfg["overload"]),
        np_backend=serve_cfg["np_backend"],
        groups_per_tenant=groups_per_tenant, obs=obs)
    srv = ServingServer(fe, credit_window=serve_cfg["credit_window"],
                        staging_high=serve_cfg["staging_high"])
    host, port = srv.start()
    return srv, fe, host, port


def observability(trace: bool):
    """Counters always; the tracer's spans only in a traced run."""
    from repro.obs import Observability

    return Observability(trace=trace, audit=False) if trace \
        else Observability.disabled()


def finished_panes(fe) -> list:
    """The engine's log of finished panes, one entry appended (``t0``,
    ``admitted``) as each pane's micro-batch has been executed, finalized
    and folded."""
    return fe._backend.rt.metrics.panes


def engine_stats(fe) -> dict:
    """The engine's own phase timers and plan-cache counts (``RunStats``)."""
    st = fe._backend.rt.stats
    return {k: getattr(st, k) for k in (
        "plan_s", "execute_s", "finalize_s", "fold_s", "plan_cache_hits",
        "plan_cache_misses", "events", "panes", "windows_emitted")}


def counters(obs) -> dict:
    return {k: v for k, v in obs.registry.collect().items()
            if isinstance(v, (int, float))}


def lost_events(fe) -> dict:
    """Events the served path shed, refused or dropped, by where."""
    rt = fe._backend.rt
    return {"session_shed": int(fe.summary()["session_shed"]),
            "engine_shed": int(sum(p.shed for p in rt.metrics.panes)),
            "ingress_rejected": int(rt.queue.rejected),
            "ingress_dropped": int(rt.queue.dropped)}
