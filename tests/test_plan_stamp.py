"""Stamped single-query graphlets.

Inside one burst, ``PaneProcessor._build_steps`` builds a single-query
graphlet once per structure class (Kleene flag, start flag, match row; no
edge mask) and stamps the class's other members from it.  These tests hold
the stamps to what ``_plan_group`` builds for each member alone: field by
field, in the cache template, in window results (against the same run with
stamping patched out), under read-only shared arrays, and in the two
``engine.plan.*`` counters.
"""

from __future__ import annotations

import copy
import dataclasses

import numpy as np
import pytest

from repro.core.engine import (HamletRuntime, PaneProcessor, RunStats,
                               _GroupPlan, _NegStep, vals_equal)
from repro.core.events import EventBatch, StreamSchema
from repro.core.optimizer import NeverShare
from repro.core.pattern import EventType, Kleene, Not, Seq
from repro.core.plan_cache import PanePlanCache
from repro.core.query import EdgePred, Pred, Query, Workload, count_star
from repro.obs import Observability
from repro.overload import OverloadConfig
from repro.overload.runtime import OverloadRuntime
from repro.streams.generator import SMARTHOME_SCHEMA, smarthome_stream

from benchmarks.common import kleene_workload

_BUILD = PaneProcessor._build_steps


def _build_all(self, plan_bursts, stats):
    """The reference: every graphlet of every burst built by _plan_group."""
    steps: list = []
    for bi, (hits, burst) in enumerate(plan_bursts):
        if hits:
            steps.append(_NegStep(hits))
        if burst is None:
            continue
        tid, el, attrs, b, q_pos, mvec, epm, groups = burst
        qpos_index = {qi: i for i, qi in enumerate(q_pos)}
        for g in groups:
            if len(g) >= 2:
                stats.shared_bursts += 1
                stats.shared_graphlets += 1
            stats.graphlets += 1
            rows = [qpos_index[qi] for qi in g]
            self._plan_group(g, el, tid, attrs, b, mvec[rows],
                             [epm[i] for i in rows], steps, stats, bi, rows)
    return steps, {}


def _assert_same(x, y, where):
    if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
        assert isinstance(x, np.ndarray) and isinstance(y, np.ndarray), where
        assert x.dtype == y.dtype and x.shape == y.shape, where
        assert np.array_equal(x, y), where
    elif isinstance(x, (list, tuple)):
        assert type(x) is type(y) and len(x) == len(y), where
        for i, (a, b) in enumerate(zip(x, y)):
            _assert_same(a, b, (where, i))
    elif isinstance(x, dict):
        assert x.keys() == y.keys(), where
        for k in x:
            _assert_same(x[k], y[k], (where, k))
    else:
        assert x == y, where


def _assert_same_steps(got, want):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert type(a) is type(b), i
        if isinstance(a, _NegStep):
            assert a.hits == b.hits, i
            continue
        for f in dataclasses.fields(_GroupPlan):
            _assert_same(getattr(a, f.name), getattr(b, f.name), (i, f.name))


class _Checker:
    """Runs the stamping build and the reference on every planned pane,
    compares them, and compares each cache template with the reference's
    stripped steps."""

    def __init__(self, monkeypatch):
        self.panes = self.graphlets = self.stamped = self.edge = 0
        self._ref = None
        chk = self

        def build(proc, plan_bursts, stats):
            st_ref = copy.deepcopy(stats)
            steps, stamped = _BUILD(proc, plan_bursts, stats)
            ref, _ = _build_all(proc, plan_bursts, st_ref)
            _assert_same_steps(steps, ref)
            assert stats == st_ref
            for i, t in stamped.items():
                assert t < i and t not in stamped
                assert steps[i].epm == [None] and len(steps[i].g) == 1
            chk.panes += 1
            chk.graphlets += sum(isinstance(s, _GroupPlan) for s in steps)
            chk.stamped += len(stamped)
            chk.edge += sum(isinstance(s, _GroupPlan) and
                            any(m is not None for m in s.epm) for s in steps)
            chk._ref = ref
            return steps, stamped

        put = PanePlanCache.put

        def put_checked(cache, key, plan):
            _assert_same_steps(plan.steps,
                               [PaneProcessor._strip(s) for s in chk._ref])
            return put(cache, key, plan)

        monkeypatch.setattr(PaneProcessor, "_build_steps", build)
        monkeypatch.setattr(PanePlanCache, "put", put_checked)


# ------------------------------------------------------------- workloads

SCHEMA = StreamSchema(types=("A", "B", "C", "X"), attrs=("v", "w"))
A, B, C, X = map(EventType, "ABCX")


def _cell_wl(n_queries=25):
    """The benchmark cell's shape: SEQ(head, Measure+) over three head
    types with no head predicate, a value predicate on every third."""
    return kleene_workload(SMARTHOME_SCHEMA, n_queries, kleene_type="Measure",
                           head_types=["Load", "Work", "Idle"],
                           pred_attr="value")


def _head_pred_wl():
    """Predicates on the head type: its members split into classes."""
    return Workload(SCHEMA, [
        Query(f"q{i}", Seq(A, Kleene(B)), aggs=(count_star(),),
              preds=({"A": [Pred("v", "<", 2.0 + (i % 3) * 3)]}
                     if i % 2 else None), within=20, slide=10)
        for i in range(6)])


def _start_flag_wl():
    """C is a non-Kleene start in some queries and a non-start in others;
    B is Kleene, a start in one query and not in the others, and a
    non-Kleene non-start in q6; C starts q3 plain and q7 as a Kleene."""
    return Workload(SCHEMA, [
        Query("q0", Seq(A, C), within=20, slide=10),
        Query("q1", Seq(C, A), within=20, slide=10),
        Query("q2", Seq(B, C), within=20, slide=10),
        Query("q3", Seq(C, Kleene(B)), within=20, slide=10),
        Query("q4", Kleene(B), within=20, slide=10),
        Query("q5", Seq(A, Kleene(B)), within=20, slide=10),
        Query("q6", Seq(A, B), within=20, slide=10),
        Query("q7", Seq(Kleene(C), Kleene(B)), within=20, slide=10),
    ])


def _neg_edge_wl():
    """A negation rule and an edge predicate: the edge-masked member takes
    the build path; its look-alikes without a mask are still stamped."""
    return Workload(SCHEMA, [
        Query("q0", Seq(A, Kleene(B)), within=20, slide=10),
        Query("q1", Seq(A, Kleene(B)), edge_preds={"B": [EdgePred("v", "<=")]},
              within=20, slide=10),
        Query("q2", Seq(A, Kleene(B), Not(X)), within=20, slide=10),
        Query("q3", Seq(A, Kleene(B)), within=20, slide=10),
        Query("q4", Seq(Kleene(B), C), within=20, slide=10),
    ])


def _stream(n=240, seed=3, groups=1):
    """A bursty stream over SCHEMA: runs of one type, small integer values
    (so predicate outcomes and edges vary inside a burst)."""
    rng = np.random.default_rng(seed)
    types, t = [], 0
    while len(types) < n:
        t = t if rng.random() < 0.6 else int(rng.integers(0, 4))
        types.extend([t] * int(rng.integers(1, 5)))
    types = np.array(types[:n], dtype=np.int32)
    attrs = rng.integers(0, 10, size=(n, 2)).astype(np.float64)
    time = np.sort(rng.integers(1, 60, size=n)).astype(np.int64)
    group = rng.integers(0, groups, size=n).astype(np.int64)
    return EventBatch(SCHEMA, types, time, attrs, group)


def _repeated(batch, pane, copies):
    """``copies`` repeats of the batch's first pane, one per pane: every
    repeat after the first plans to the same cache key."""
    first = batch.time_slice(int(batch.time.min()) // pane * pane,
                             (int(batch.time.min()) // pane + 1) * pane)
    parts = []
    for c in range(copies):
        parts.append(EventBatch(first.schema, first.type_id,
                                first.time + c * pane, first.attrs,
                                first.group))
    return EventBatch(first.schema,
                      np.concatenate([p.type_id for p in parts]),
                      np.concatenate([p.time for p in parts]),
                      np.concatenate([p.attrs for p in parts]),
                      np.concatenate([p.group for p in parts]))


def _case(name):
    if name == "cell":
        return _cell_wl(), None, smarthome_stream(
            events_per_minute=400, minutes=3, n_groups=3, seed=5)
    if name == "cell_never_share":
        return _cell_wl(), NeverShare(), smarthome_stream(
            events_per_minute=400, minutes=2, n_groups=2, seed=7)
    if name == "head_pred":
        return _head_pred_wl(), None, _stream(seed=4)
    if name == "start_flags":
        return _start_flag_wl(), NeverShare(), _stream(seed=5)
    return _neg_edge_wl(), NeverShare(), _stream(seed=6)


CASES = ["cell", "cell_never_share", "head_pred", "start_flags", "neg_edge"]


def _t_end(wl, batch):
    rt = HamletRuntime(wl)
    return ((int(batch.time.max()) + rt.pane) // rt.pane) * rt.pane


# ------------------------------------------------- (a) field by field


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("plan_cache", [False, True])
def test_stamped_steps_equal_their_own_build(monkeypatch, name, plan_cache):
    wl, policy, batch = _case(name)
    chk = _Checker(monkeypatch)
    HamletRuntime(wl, policy=policy, plan_cache=plan_cache).run(
        batch, _t_end(wl, batch))
    assert chk.panes > 0
    assert chk.stamped > 0, "the case never stamped"
    if name == "neg_edge":
        assert chk.edge > 0


def test_head_predicates_split_classes(monkeypatch):
    """Members whose head predicate outcomes differ are built apart: more
    than one built step per head burst, and still stamps among equals."""
    wl, policy, batch = _case("head_pred")
    seen = []

    def build(proc, plan_bursts, stats):
        steps, stamped = _BUILD(proc, plan_bursts, stats)
        a = wl.schema.type_id("A")
        built_a = [s for i, s in enumerate(steps) if isinstance(s, _GroupPlan)
                   and s.type_id == a and i not in stamped]
        seen.append(len({s.bi for s in built_a}) < len(built_a))
        return steps, stamped

    monkeypatch.setattr(PaneProcessor, "_build_steps", build)
    HamletRuntime(wl, policy=policy, plan_cache=False).run(
        batch, _t_end(wl, batch))
    assert any(seen)


# ------------------------------------ (b) window results, stamping off


def _run(wl, policy, batch, t_end, kind, obs=None):
    if kind == "overload":
        rt = OverloadRuntime(wl, OverloadConfig(shed_policy="none",
                                                micro_batch=4),
                             policy=policy, obs=obs)
        out = rt.run(batch, t_end)
        rt.shutdown()
        return out, rt.stats
    rt = HamletRuntime(wl, policy=policy, plan_cache=kind == "cached",
                       obs=obs)
    return rt.run(batch, t_end), rt.stats


def _assert_bitwise(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert vals_equal(a[k], b[k]), k


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("kind", ["uncached", "cached", "overload"])
def test_window_results_bitwise_without_stamping(monkeypatch, name, kind):
    wl, policy, batch = _case(name)
    t_end = _t_end(wl, batch)
    got, st = _run(wl, copy.deepcopy(policy), batch, t_end, kind)
    with monkeypatch.context() as m:
        m.setattr(PaneProcessor, "_build_steps", _build_all)
        want, st_want = _run(wl, copy.deepcopy(policy), batch, t_end, kind)
    _assert_bitwise(got, want)
    for f in RunStats.COUNT_FIELDS + ("graphlets", "shared_graphlets",
                                      "plan_cache_hits", "plan_cache_misses"):
        assert getattr(st, f) == getattr(st_want, f), f


@pytest.mark.parametrize("kind", ["cached", "overload"])
def test_window_results_bitwise_on_plan_cache_hits(monkeypatch, kind):
    wl, policy, batch = _case("cell")
    pane = HamletRuntime(wl).pane
    batch = _repeated(batch, pane, 6)
    t_end = _t_end(wl, batch)
    got, st = _run(wl, policy, batch, t_end, kind)
    assert st.plan_cache_hits > 0
    with monkeypatch.context() as m:
        m.setattr(PaneProcessor, "_build_steps", _build_all)
        want, _ = _run(wl, policy, batch, t_end, kind)
    _assert_bitwise(got, want)


# ------------------------------------ (c) shared arrays stay unwritten


def _freeze(step):
    for f in ("attrs", "mvec", "div", "div_rows", "live", "dead", "em",
              "base_c"):
        a = getattr(step, f)
        if isinstance(a, np.ndarray):
            a.flags.writeable = False
    for _ui, vals in step.sum_units:
        if isinstance(vals, np.ndarray):
            vals.flags.writeable = False


@pytest.mark.parametrize("kind", ["uncached", "cached", "overload"])
def test_shared_arrays_are_never_written(monkeypatch, kind):
    """Every array a stamp shares with its template is read-only through
    execute, finalize and fold on the np backend; the results still match
    the run without stamping."""
    wl, policy, batch = _case("cell")
    t_end = _t_end(wl, batch)
    with monkeypatch.context() as m:
        m.setattr(PaneProcessor, "_build_steps", _build_all)
        want, _ = _run(wl, policy, batch, t_end, kind)
    frozen = []

    def build(proc, plan_bursts, stats):
        steps, stamped = _BUILD(proc, plan_bursts, stats)
        for i, t in stamped.items():
            _freeze(steps[i])
            _freeze(steps[t])
            frozen.append(i)
        return steps, stamped

    monkeypatch.setattr(PaneProcessor, "_build_steps", build)
    got, _ = _run(wl, policy, batch, t_end, kind)
    assert frozen
    _assert_bitwise(got, want)


# ---------------------------------------------------------- (d) counters


def _pane(evs):
    n = len(evs)
    return EventBatch(SCHEMA, np.array([t for t, _ in evs], dtype=np.int32),
                      np.arange(1, n + 1),
                      np.array([[float(v), 0.0] for _, v in evs]))


def test_counters_count_built_and_stamped():
    """Three predicate-free queries SEQ(A, B+) under NeverShare over the
    pane A A B B: each burst builds one graphlet and stamps two."""
    wl = Workload(SCHEMA, [Query(f"q{i}", Seq(A, Kleene(B)), within=20,
                                 slide=10) for i in range(3)])
    obs = Observability.disabled()
    rt = HamletRuntime(wl, policy=NeverShare(), plan_cache=False, obs=obs)
    rt.run(_pane([(0, 1), (0, 2), (1, 3), (1, 4)]), 10)
    c = obs.registry.collect()
    assert c["engine.plan.graphlets"] == 6
    assert c["engine.plan.graphlets_stamped"] == 4


@pytest.mark.parametrize("name", CASES)
def test_counters_match_the_planned_steps(monkeypatch, name):
    """Over whole runs, with plan-cache hits: the counters sum what the
    misses built and stamped, and a hit counts nothing."""
    wl, policy, batch = _case(name)
    batch = _repeated(batch, HamletRuntime(wl).pane, 4) \
        if name == "cell" else batch
    chk = _Checker(monkeypatch)
    obs = Observability.disabled()
    _, st = _run(wl, policy, batch, _t_end(wl, batch), "cached", obs=obs)
    c = obs.registry.collect()
    assert chk.panes == st.plan_cache_misses
    assert c["engine.plan.graphlets"] == chk.graphlets
    assert c["engine.plan.graphlets_stamped"] == chk.stamped > 0
    if name == "cell":
        assert st.plan_cache_hits > 0
