"""FoldExecutor differentials and golden tests.

The stacked finalize/fold path (``core/fold_exec.py``) must be **bitwise
identical** to the sequential per-graphlet replay (``fold_exec=False``) —
across the four named workload streams, the three disorder models, micro
batch K in {1, 4, 16}, the overload path, and the service.  The ragged
golden tests pin the bucket mechanics: a single graphlet, mixed burst
shapes in one ragged d == 0 bucket, a negation step splitting the level
schedule mid-pane, and the empty pane.

Quick representatives run in the fast lane; the full sweeps carry ``slow``.
"""

import numpy as np
import pytest

from repro.core.engine import (HamletRuntime, PaneMicroBatcher, RunStats,
                               fold_panes, vals_equal)
from repro.core.events import EventBatch, StreamSchema
from repro.core.fold_exec import FoldExecutor, build_fold_schedule, _levelize
from repro.core.optimizer import AlwaysShare, DynamicPolicy
from repro.core.pattern import EventType, Kleene, Not, Seq
from repro.core.query import (Pred, Query, Workload, agg_avg, agg_sum,
                              count_star)
from repro.core.service import HamletService
from repro.eventtime import EventTimeConfig, EventTimeRuntime
from repro.overload import OverloadConfig
from repro.overload.runtime import OverloadRuntime
from repro.streams.generator import (NAMED_STREAMS, DisorderConfig,
                                     apply_disorder)

from benchmarks.common import kleene_workload

KS = (1, 4, 16)

WORKLOAD_SHAPE = {
    "ridesharing": dict(kleene_type="Travel",
                        head_types=["Request", "Pickup", "Dropoff"]),
    "stock": dict(kleene_type="Quote", head_types=["Buy", "Sell"]),
    "smarthome": dict(kleene_type="Measure", head_types=["Load", "Work"]),
    "taxi": dict(kleene_type="Travel", head_types=["Request", "Pickup"]),
}


def _schema_for(name):
    from repro.streams import generator as G

    return {"ridesharing": G.RIDESHARING_SCHEMA, "stock": G.STOCK_SCHEMA,
            "smarthome": G.SMARTHOME_SCHEMA, "taxi": G.TAXI_SCHEMA}[name]


def _named_case(name, epm=250, minutes=2, n_queries=4, pred=True):
    schema = _schema_for(name)
    wl = kleene_workload(
        schema, n_queries, **WORKLOAD_SHAPE[name], within=60, slide=30,
        pred_attr=list(schema.attrs)[0] if pred else None)
    stream = NAMED_STREAMS[name](events_per_minute=epm, minutes=minutes,
                                 seed=13)
    t_end = ((int(stream.time.max()) + 30) // 30) * 30
    return wl, stream, t_end


def _assert_bitwise(a, b, tag=""):
    assert a.keys() == b.keys(), tag
    for k in a:
        assert vals_equal(a[k], b[k]), (tag, k)


# ------------------------------------------------------- runtime sweeps


def _sweep_runtime(name):
    wl, stream, t_end = _named_case(name)
    want = HamletRuntime(wl, fold_exec=False, plan_cache=False).run(
        stream, t_end)
    for K in KS:
        for pc in (False, True):
            got = HamletRuntime(wl, micro_batch=K, plan_cache=pc,
                                fold_exec=True).run(stream, t_end)
            _assert_bitwise(got, want, (name, K, pc))


def test_fold_exec_bitwise_ridesharing():
    _sweep_runtime("ridesharing")


@pytest.mark.slow
@pytest.mark.parametrize("name", ["stock", "smarthome", "taxi"])
def test_fold_exec_bitwise_named(name):
    _sweep_runtime(name)


# ------------------------------------------------------------ event time


def _sweep_disorder(name, model):
    wl, stream, t_end = _named_case(name)
    want = HamletRuntime(wl, fold_exec=False, plan_cache=False).run(
        stream, t_end)
    ds = apply_disorder(stream, DisorderConfig(model=model, fraction=0.2,
                                               seed=2))
    cfg = EventTimeConfig(watermark="bounded_skew",
                          skew=max(ds.max_lateness(), 1), speculative=True)
    for K in KS:
        et = EventTimeRuntime(wl, cfg, micro_batch=K, fold_exec=True)
        got = et.run_disordered(ds.base, ds.order, chunk=64, t_end=t_end)
        _assert_bitwise(got, want, (name, model, K))
        # the batched window folds actually ran through the executor
        assert et.rt.fold_exec.window_folds > 0


def test_fold_exec_disordered_bounded_skew():
    _sweep_disorder("ridesharing", "bounded_skew")


@pytest.mark.slow
@pytest.mark.parametrize("model", ["stragglers", "adversarial_tail"])
def test_fold_exec_disordered_models(model):
    _sweep_disorder("ridesharing", model)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["stock", "smarthome", "taxi"])
def test_fold_exec_disordered_named(name):
    _sweep_disorder(name, "bounded_skew")


# ------------------------------------------------- overload and service


def test_fold_exec_overload_bitwise():
    wl, stream, t_end = _named_case("ridesharing", epm=400, pred=False)
    base_cfg = dict(slo_ms=50.0, shed_policy="benefit_weighted",
                    fixed_shed=0.3)
    want = OverloadRuntime(wl, OverloadConfig(
        **base_cfg, micro_batch=1, plan_cache=False,
        fold_exec=False)).run(stream, t_end)
    for K in KS:
        got = OverloadRuntime(wl, OverloadConfig(
            **base_cfg, micro_batch=K, plan_cache=True,
            fold_exec=True)).run(stream, t_end)
        _assert_bitwise(got, want, ("overload", K))


def test_fold_exec_service_bitwise():
    wl, stream, t_end = _named_case("ridesharing", epm=200)
    queries = list(wl.queries)
    outs = []
    for fe, K in ((False, 1), (True, 4), (True, 16)):
        svc = HamletService(wl.schema, queries, micro_batch=K, fold_exec=fe)
        svc.feed(stream)
        svc.close()
        outs.append(dict(svc.results))
    _assert_bitwise(outs[1], outs[0], "service K=4")
    _assert_bitwise(outs[2], outs[0], "service K=16")


# --------------------------------------------------- ragged golden tests

SCHEMA = StreamSchema(types=("A", "B", "C", "X"), attrs=("v",))
A, B, C, X = map(EventType, "ABCX")


def _batch(evs, t0=1):
    n = len(evs)
    types = np.array([t for t, _ in evs], dtype=np.int32)
    attrs = np.array([[float(v)] for _, v in evs]).reshape(n, 1) if n else None
    return EventBatch(SCHEMA, types, np.arange(t0, t0 + n), attrs)


def _golden_wl():
    return Workload(SCHEMA, [
        Query("q1", Seq(A, Kleene(B)), aggs=(count_star(), agg_sum("B", "v")),
              within=40, slide=20),
        Query("q2", Seq(C, Kleene(B)), preds={"B": [Pred("v", "<", 3)]},
              within=40, slide=20),
        Query("q3", Kleene(B), within=40, slide=20),
    ])


def _run_both(wl, evs, t_end=40):
    batch = _batch(evs)
    off = HamletRuntime(wl, policy=DynamicPolicy(), fold_exec=False,
                        plan_cache=False).run(batch, t_end)
    on = HamletRuntime(wl, policy=DynamicPolicy(), fold_exec=True,
                       plan_cache=True).run(batch, t_end)
    _assert_bitwise(on, off)
    return on


def test_golden_single_graphlet():
    _run_both(_golden_wl(), [(1, 1)] * 5)          # one B-burst, one pane


def test_golden_mixed_shapes_ragged_bucket():
    # bursts of different lengths land in one ragged d == 0 bucket; the
    # divergent q2 predicate (v >= 3) adds a d > 0 bucket alongside
    evs = ([(0, 1)] + [(1, 1)] * 3 + [(2, 1)] + [(1, 2)] * 7
           + [(0, 1)] + [(1, 4)] * 2 + [(1, 1)] * 11)
    _run_both(_golden_wl(), evs)


def test_golden_negation_split_mid_pane():
    wl = Workload(SCHEMA, [
        Query("q1", Seq(A, Kleene(B), Not(X)), within=40, slide=20),
        Query("q2", Seq(A, Not(X), Kleene(B)), within=40, slide=20),
        Query("q3", Seq(A, Kleene(B)), within=40, slide=20),
    ])
    evs = ([(0, 1)] + [(1, 1)] * 4 + [(3, 1)]       # X fires mid-pane
           + [(1, 1)] * 5 + [(3, 1)] + [(1, 1)] * 3)
    _run_both(wl, evs)
    # the schedule really splits at the negation step: the _NegStep level
    # sits strictly between its neighbours' group levels
    rt = HamletRuntime(wl, fold_exec=True, plan_cache=False)
    proc = rt.make_processor(0)
    steps = proc.plan(_batch(evs), RunStats())
    sched = build_fold_schedule(rt.ctxs[0], steps)
    assert sum(len(n) for n in sched.neg) >= 1
    neg_levels = [lv for lv in range(sched.n_levels) if sched.neg[lv]]
    assert neg_levels and 0 < min(neg_levels) < sched.n_levels - 1


def test_golden_empty_pane():
    wl = _golden_wl()
    rt = HamletRuntime(wl, fold_exec=True)
    empty = EventBatch(SCHEMA, np.array([], np.int32),
                       np.array([], np.int64), None)
    M_on = rt.make_processor(0).process(empty, RunStats())
    rt_off = HamletRuntime(wl, fold_exec=False)
    M_off = rt_off.make_processor(0).process(empty, RunStats())
    assert np.array_equal(M_on, M_off)
    # an event-free pane is the identity on every query's state
    u0 = rt.ctxs[0].layout.fresh_state()
    for ci in range(M_on.shape[0]):
        assert np.array_equal(u0 @ M_on[ci].T, u0)


# ------------------------------------------------------- level schedule


def test_levelize_serializes_query_chains():
    class _G:
        def __init__(self, g):
            self.g = g

    # two interleaved disjoint chains share levels; overlap serializes
    steps = [_G([0, 1]), _G([2]), _G([0]), _G([1, 2]), _G([0, 1, 2])]
    assert _levelize(steps) == [0, 0, 1, 1, 2]


# ------------------------------------------------ stacked window folds


def test_fold_windows_matches_fold_panes():
    rng = np.random.default_rng(3)
    fe = FoldExecutor()
    folds = []
    for n, C in [(1, 4), (3, 4), (3, 4), (7, 6), (0, 5)]:
        u0 = rng.standard_normal(C)
        Ms = [rng.standard_normal((C, C)) for _ in range(n)]
        folds.append((u0, Ms))
    got = fe.fold_windows(folds)
    for (u0, Ms), u in zip(folds, got):
        assert np.array_equal(u, fold_panes(Ms, u0))
    # same-shape chains shared a stacked launch
    assert fe.window_folds == 3


def test_fold_windows_device_backend_pads_and_returns_host_dtype():
    rng = np.random.default_rng(4)
    fe = FoldExecutor(backend="jax")
    # 3 same-shape chains: one launch, batch padded to 4 on the device
    folds = [(rng.standard_normal(4), [rng.standard_normal((4, 4))
                                       for _ in range(3)]) for _ in range(3)]
    got = fe.fold_windows(folds)
    for (u0, Ms), u in zip(folds, got):
        assert u.dtype == u0.dtype
        np.testing.assert_allclose(u, fold_panes(Ms, u0), rtol=1e-12)
    assert fe.window_folds == 1


# -------------------------------------------------- flush-plan caching


def test_flush_plan_cache_reused_on_repeated_shapes():
    wl = _golden_wl()
    rt = HamletRuntime(wl, micro_batch=4, plan_cache=True, fold_exec=True)
    evs = [(0, 1)] + [(1, 1)] * 6
    batch = _batch(evs)
    stats = RunStats()
    proc = rt.make_processor(0)

    def flush_k4():
        mb = PaneMicroBatcher(rt.executor, k=4, fold_exec=rt.fold_exec)
        pends = [mb.submit(proc, batch, stats) for _ in range(4)]
        mb.drain()
        return [p.finalize() for p in pends]

    first = flush_k4()
    l1 = rt.fold_exec.launches
    plans = len(rt.fold_exec._plans)
    second = flush_k4()
    # identical schedule combination: the merged flush plan is reused and
    # the per-flush launch count stays constant
    assert len(rt.fold_exec._plans) == plans
    assert rt.fold_exec.launches == 2 * l1
    for a, b in zip(first, second):
        assert np.array_equal(a, b)


# ------------------------------------- stacked event-snapshot fills (SUM)


def _ride_stream(epm=600, minutes=1, seed=2**31 + 7):
    from repro.streams.generator import (RIDESHARING_SCHEMA,
                                         TenantStreamConfig, tenant_stream)

    return tenant_stream(TenantStreamConfig(
        schema=RIDESHARING_SCHEMA, n_tenants=4, groups_per_tenant=2,
        base_events_per_minute=epm, minutes=minutes, burstiness=0.85,
        type_weights=(1, 1, 6, 1, 1, 1), seed=seed))


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("plan_cache", [False, True])
def test_stacked_fill_bitwise_rideshare(K, plan_cache):
    """The ridesharing deployment's shape — SEQ(Request, Travel+, X) for X
    in {NOT Pickup, Dropoff, Cancel}, COUNT with SUM(duration) or
    AVG(speed), a speed predicate of its own on Travel in each copy — folds
    its divergent buckets in the stacked form, bit for bit as the
    sequential replay."""
    from repro.launch.hamlet_service import ridesharing_workload

    wl = ridesharing_workload(7)
    stream = _ride_stream()
    t_end = ((int(stream.time.max()) // 5) + 1) * 5
    want = HamletRuntime(wl, fold_exec=False, plan_cache=False).run(
        stream, t_end)
    rt = HamletRuntime(wl, micro_batch=K, plan_cache=plan_cache,
                       fold_exec=True)
    _assert_bitwise(rt.run(stream, t_end), want, (K, plan_cache))
    assert rt.fold_exec.fill_stacked > 0


def test_count_only_fills_stay_sequential():
    """COUNT alone has no sum column to stack: every divergent bucket keeps
    the column-by-column fill, and the results are the replay's."""
    wl, stream, t_end = _named_case("ridesharing")
    want = HamletRuntime(wl, policy=AlwaysShare(), fold_exec=False,
                         plan_cache=False).run(stream, t_end)
    rt = HamletRuntime(wl, policy=AlwaysShare(), micro_batch=4,
                       fold_exec=True)
    _assert_bitwise(rt.run(stream, t_end), want)
    assert rt.fold_exec.fill_stacked == 0
    assert rt.fold_exec.fill_sequential > 0


def test_stacked_fill_overflow_matches_replay():
    """SUM values past float64's range put inf into the sum round's
    coefficients, and ``0 * inf`` into the fills: the stacked form leaves
    NaN where the sequential replay does, and the finite windows alike."""
    wl = Workload(SCHEMA, [
        Query("q1", Seq(A, Kleene(B)), aggs=(count_star(), agg_sum("B", "v")),
              within=40, slide=20),
        Query("q2", Seq(A, Kleene(B)), aggs=(count_star(), agg_sum("B", "v")),
              preds={"B": [Pred("v", "<", 3)]}, within=40, slide=20),
        Query("q3", Seq(C, Kleene(B)), aggs=(count_star(), agg_avg("B", "v")),
              within=40, slide=20),
    ])
    evs = ([(0, 1), (2, 1)] + [(1, 1e307), (1, 1)] * 8 + [(0, 1)]
           + [(1, 2e307), (1, 2), (1, 1)] * 6)
    batch = _batch(evs)
    off = HamletRuntime(wl, policy=AlwaysShare(), fold_exec=False,
                        plan_cache=False).run(batch, 40)
    rt = HamletRuntime(wl, policy=AlwaysShare(), fold_exec=True,
                       micro_batch=4)
    on = rt.run(batch, 40)
    assert on.keys() == off.keys()
    assert all(vals_equal(on[k], off[k]) for k in off)
    vals = [v for r in on.values() for v in r.values()]
    assert any(np.isnan(v) for v in vals) and any(np.isfinite(v)
                                                   for v in vals)
    assert rt.fold_exec.fill_stacked > 0


def _ref_fill(W, used, nu, b, div, mvec, epm, coefs, start, sum_v):
    """One member's event-snapshot fills as ``PaneProcessor.finalize``
    replays them: a rank-1 update of every unit's ``P`` per filled
    column, column by column."""
    P = [c @ W for c in coefs]

    def fill(zcol, f):
        W[zcol] = f
        for k, c in enumerate(coefs):
            col = c[:, zcol]
            if col.any():
                P[k] += np.outer(col, f)

    adj = np.tril(np.ones((b, b), dtype=bool), k=-1)
    if epm is not None:
        adj &= epm
    adj &= mvec[None, :]
    C = W.shape[1]
    for r, i in enumerate(div):
        row = adj[i].astype(float)
        zc = 1 + nu + r * nu
        f_c = (start * W[0] + W[1] + row @ P[0] if mvec[i]
               else np.zeros(C))
        fill(zc, f_c)
        for pos, ui in enumerate(used[1:]):
            if mvec[i]:
                f_s = W[1 + ui] + row @ P[1 + pos]
                if sum_v[pos] is not None:
                    f_s = f_s + sum_v[pos][i] * f_c
            else:
                f_s = np.zeros(C)
            fill(zc + ui, f_s)


def _hand_bucket(seed, cross=False, inf=False):
    """Two groups (3 and 2 members) of one divergent bucket: count plus
    two sum units, coefficients drawn so that a count column feeds every
    unit but one group's third unit, and each sum column only its own
    unit's ``P`` — unless ``cross`` puts one non-zero of a sum column into
    another unit's coefficients."""
    from types import SimpleNamespace

    rng = np.random.default_rng(seed)
    nu, used, b, d, C = 3, (0, 1, 2), 6, 3, 5
    B = 1 + nu + d * nu
    sizes = (3, 2)
    gof = np.repeat(np.arange(len(sizes)), sizes)
    div_g = np.array([[1, 3, 4], [0, 2, 5]])
    steps_g, coef_g = [], []
    for g, n in enumerate(sizes):
        mvec = rng.random((n, b)) < 0.8
        mvec[:, div_g[g]] |= rng.random((n, d)) < 0.5
        epm = [None if m % 2 else rng.random((b, b)) < 0.7
               for m in range(n)]
        vals = rng.uniform(0.5, 2.0, b)
        steps_g.append(SimpleNamespace(
            mvec=mvec, epm=epm, sum_units=[(1, vals), (2, None)]))
        coefs = []
        for k, u in enumerate(used):
            c = rng.standard_normal((b, B))
            for r in range(d):
                zc = 1 + nu + r * nu
                c[: div_g[g, r] + 1, zc:zc + nu] = 0.0
                for ui in used[1:]:
                    if ui != u:
                        c[:, zc + ui] = 0.0
            if g == 1 and k == 2:
                c[:, 1 + nu::nu] = 0.0      # the count columns skip unit 2
            coefs.append(c)
        coef_g.append(coefs)
    if cross:
        coef_g[0][1][5, 1 + nu + nu + 2] = 0.25   # row 1's unit-2 column
    W = np.zeros((len(gof), B, C))
    W[:, :1 + nu] = rng.standard_normal((len(gof), 1 + nu, C))
    if inf:
        coef_g[1][0][4, 1 + nu] = np.inf
        W[0, 2, 1] = np.inf
    start = (rng.random(len(gof)) < 0.5).astype(float)
    ctx = SimpleNamespace(nu=nu)
    return ctx, W, used, b, d, div_g, gof, steps_g, coef_g, start


@pytest.mark.parametrize("cross, inf, stacked", [
    (False, False, True),
    (True, False, False),
    (False, True, True),
])
def test_fill_forms_match_the_member_replay(cross, inf, stacked):
    """The stacked form runs where no sum column crosses units and the
    column-by-column form where one does; both equal the per-member replay
    bit for bit, inf and NaN placement included."""
    ctx, W, used, b, d, div_g, gof, steps_g, coef_g, start = _hand_bucket(
        5, cross, inf)
    want = W.copy()
    with np.errstate(over="ignore", invalid="ignore"):
        got = FoldExecutor._fill_snapshots(
            ctx, W, W[:, 0].copy(), used, b, d, div_g=div_g, gof=gof,
            steps_g=steps_g, coef_g=coef_g, start_m=start)
        seen = {}
        for m, g in enumerate(gof):
            i = seen[g] = seen.get(g, -1) + 1
            s = steps_g[g]
            _ref_fill(want[m], used, ctx.nu, b, div_g[g], s.mvec[i],
                      s.epm[i], coef_g[g], start[m],
                      [v for _, v in s.sum_units])
    assert got is stacked
    if inf:
        assert np.isnan(want).any() and np.isinf(want).any()
    assert np.array_equal(W.view(np.int64), want.view(np.int64))
