"""Sharded multi-tenant service: differential contract (N-shard results
bitwise equal to 1-shard on the same admitted set), placement/rebalance,
cross-shard watermark alignment, global admission certificates, and the
merged read side."""

import numpy as np
import pytest

from repro.core.engine import RunStats
from repro.core.pattern import EventType, Kleene, Seq
from repro.core.query import Query, Workload
from repro.overload import OverloadConfig
from repro.overload.accountant import ErrorAccountant, merge_error_reports
from repro.shardsvc import (ADMISSION_MODES, PlacementTable,
                            ShardedHamletService, ShardServiceConfig,
                            WatermarkAligner, ring_hash)
from repro.streams.generator import (NAMED_STREAMS, RIDESHARING_SCHEMA,
                                     SMARTHOME_SCHEMA, STOCK_SCHEMA,
                                     TAXI_SCHEMA, DisorderConfig,
                                     apply_disorder)

# (schema, kleene type, head types) per named dataset — the four workloads
# the differential contract is pinned on
DATASETS = {
    "ridesharing": (RIDESHARING_SCHEMA, "Travel", ("Request", "Accept")),
    "stock": (STOCK_SCHEMA, "Quote", ("Buy", "Sell")),
    "smarthome": (SMARTHOME_SCHEMA, "Measure", ("Load", "Work")),
    "taxi": (TAXI_SCHEMA, "Travel", ("Request", "Pickup")),
}

STREAM_KW = {"ridesharing": dict(events_per_minute=250, minutes=2,
                                 n_groups=6),
             "stock": dict(events_per_minute=300, minutes=2, n_groups=6),
             "smarthome": dict(events_per_minute=400, minutes=2,
                               n_groups=8),
             "taxi": dict(events_per_minute=250, minutes=2, n_groups=6)}


def _wl(schema, kleene, heads, within=20, slide=10):
    k = EventType(kleene)
    qs = [Query(f"q{i}", Seq(EventType(h), Kleene(k)),
                within=within, slide=slide)
          for i, h in enumerate(heads)]
    qs.append(Query("qk", Kleene(k), within=within, slide=slide))
    return Workload(schema, qs)


def _dataset(name):
    schema, kleene, heads = DATASETS[name]
    return (_wl(schema, kleene, heads),
            NAMED_STREAMS[name](**STREAM_KW[name]))


def _cfg(n_shards, **kw):
    kw.setdefault("admission", "none")
    kw.setdefault("overload",
                  OverloadConfig(shed_policy="none", micro_batch=4))
    return ShardServiceConfig(n_shards=n_shards, **kw)


def _assert_same_results(a: dict, b: dict):
    assert set(a) == set(b)
    for key in a:
        assert np.array_equal(a[key], b[key]), key


# ------------------------------------------------------------- differential


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_shard_count_invariant_results(name):
    """2- and 4-shard runs are permutation-stable bitwise matches of the
    1-shard run, and the fleet RunStats count fields agree."""
    wl, stream = _dataset(name)
    runs = {}
    counts = {}
    for n in (1, 2, 4):
        svc = ShardedHamletService(wl, _cfg(n))
        runs[n] = svc.run(stream)
        counts[n] = svc.stats().counts()
    _assert_same_results(runs[1], runs[2])
    _assert_same_results(runs[1], runs[4])
    assert counts[1] == counts[2] == counts[4]
    assert runs[1], "differential is vacuous without results"


def test_chunk_size_invariant():
    """Routing in bigger arrival chunks (several panes at once) does not
    change results — safe stepping never runs an incomplete pane."""
    wl, stream = _dataset("ridesharing")
    svc_small = ShardedHamletService(wl, _cfg(2))
    svc_big = ShardedHamletService(wl, _cfg(2))
    r_small = svc_small.run(stream)
    r_big = svc_big.run(stream, chunk_ticks=3 * svc_big.pane)
    _assert_same_results(r_small, r_big)


def test_fixed_shed_differential_and_certificates():
    """global_fixed admission sheds pane-by-pane on the full chunk before
    routing, so the admitted set — and therefore the results and the global
    error certificate — are shard-count invariant."""
    wl, stream = _dataset("stock")
    runs, reports = {}, {}
    for n in (1, 2, 4):
        svc = ShardedHamletService(wl, _cfg(
            n, admission="global_fixed",
            overload=OverloadConfig(shed_policy="drop_tail",
                                    fixed_shed=0.3, micro_batch=4)))
        runs[n] = svc.run(stream)
        reports[n] = svc.error_report()
        assert svc.admission.summary()["shed"] > 0
    _assert_same_results(runs[1], runs[2])
    _assert_same_results(runs[1], runs[4])
    assert reports[1] == reports[2] == reports[4]


@pytest.mark.parametrize("model,fraction,lossless", [
    ("bounded_skew", 0.2, True),
    ("stragglers", 0.15, False),
])
def test_eventtime_disorder_differential(model, fraction, lossless):
    """Disordered arrival through per-shard reorder buffers: results and
    late/expired accounting are shard-count invariant.  With skew covering
    the max lateness nothing is lost; with lossy stragglers every shard
    count drops the identical late set (the router watermark equals the
    1-shard watermark)."""
    wl, stream = _dataset("taxi")
    ds = apply_disorder(stream, DisorderConfig(
        model=model, fraction=fraction, max_skew=6, straggler_delay=25,
        seed=5))
    skew = ds.max_lateness() if lossless else 6
    runs, lost = {}, {}
    for n in (1, 2, 4):
        svc = ShardedHamletService(wl, _cfg(n, eventtime=True, skew=skew))
        runs[n] = svc.run_chunks(ds.chunks(64))
        lost[n] = (sum(w.late_total for w in svc.workers),
                   sum(w.expired_total for w in svc.workers))
    _assert_same_results(runs[1], runs[2])
    _assert_same_results(runs[1], runs[4])
    assert lost[1] == lost[2] == lost[4]
    if lossless:
        assert lost[1] == (0, 0)
    else:
        assert lost[1][0] > 0


# ---------------------------------------------------------------- rebalance


def test_rebalance_is_exact():
    """A mid-stream targeted move of one group produces results bitwise
    equal to never moving it, and lands in the placement overrides."""
    wl, stream = _dataset("ridesharing")
    t_hi = int(stream.time.max()) + 1

    baseline = ShardedHamletService(wl, _cfg(2)).run(stream)

    svc = ShardedHamletService(wl, _cfg(2))
    group = 3
    src = svc.placement.shard_of(group)
    dst = 1 - src
    boundary = None
    records = []
    for t0 in range(0, t_hi, svc.pane):
        records += svc.ingest(stream.time_slice(t0, t0 + svc.pane))
        if boundary is None and t0 >= t_hi // 2:
            boundary = svc.plan_rebalance(group, dst)
    records += svc.close()
    assert boundary is not None and boundary % svc.pane == 0
    assert svc.placement.overrides == {group: dst}
    assert svc.placement.shard_of(group) == dst
    assert not svc._moves, "move never committed"
    _assert_same_results(baseline, svc.results())
    # the moved group's windows are handed over once each, in close order
    emitted = {(r.query, r.group, r.w0): r.vals for r in records}
    assert len(emitted) == len(records)
    _assert_same_results(baseline, emitted)
    moved = [(r.query, r.w0) for r in records if r.group == group]
    for q in {q for q, _ in moved}:
        w0s = [w0 for qq, w0 in moved if qq == q]
        assert w0s == sorted(w0s), q


def test_rebalance_committed_in_close_hands_over_in_close_order():
    """A move held back by a stalled source shard commits while ``close``
    drives the shards out, and the target then closes the group's later
    windows in the same call: the source's windows of the group are still
    handed over first, whatever the shards' order."""
    wl, stream = _dataset("ridesharing")
    t_hi = int(stream.time.max()) + 1
    svc = ShardedHamletService(wl, _cfg(2))
    group = next(g for g in range(6) if svc.placement.shard_of(g) == 1)
    records = []
    for t0 in range(0, t_hi, svc.pane):
        records += svc.ingest(stream.time_slice(t0, t0 + svc.pane))
        if t0 == t_hi // 2 // svc.pane * svc.pane:
            svc.plan_rebalance(group, 0)
            svc.workers[1].throttle = 0      # the source stalls
    assert svc._moves, "the move committed before close"
    records += svc.close()
    assert svc.placement.shard_of(group) == 0
    emitted = {(r.query, r.group, r.w0): r.vals for r in records}
    assert len(emitted) == len(records)
    _assert_same_results(svc.results(), emitted)
    for q in {r.query for r in records}:
        w0s = [r.w0 for r in records if r.group == group and r.query == q]
        assert w0s == sorted(w0s), q


def test_rebalance_to_same_shard_is_noop():
    wl, stream = _dataset("ridesharing")
    svc = ShardedHamletService(wl, _cfg(2))
    group = 3
    src = svc.placement.shard_of(group)
    svc.plan_rebalance(group, src)
    assert not svc._moves and svc.placement.overrides == {}
    svc.run(stream)


# ---------------------------------------------------- watermark alignment


def test_laggard_excluded_and_alignment_advances():
    """A throttled shard is excluded from alignment once it trails by more
    than max_lag_epochs: the aligned frontier keeps advancing with the
    healthy shards instead of pinning to the global min."""
    wl, stream = _dataset("smarthome")
    svc = ShardedHamletService(wl, _cfg(4, align_every_panes=1,
                                        max_lag_epochs=1))
    svc.workers[0].throttle = 1
    t_hi = int(stream.time.max()) + 1
    max_lead, was_laggard, saw_pending = 0, False, False
    for t0 in range(0, t_hi, 6 * svc.pane):
        svc.ingest(stream.time_slice(t0, t0 + 6 * svc.pane))
        st = svc.aligner.status()
        max_lead = max(max_lead,
                       st["aligned_time"] - svc.workers[0].t_now)
        was_laggard = was_laggard or 0 in st["laggards"]
        final, pending = svc.aligned_results()
        saw_pending = saw_pending or bool(pending)
        # every final window closed at or before the aligned frontier
        for (qname, _gk, w0) in final:
            assert w0 + svc._within[qname] <= st["aligned_time"]
    svc.close()
    assert was_laggard and max_lead > 0
    # after close the laggard rejoined and alignment covers every shard
    st = svc.aligner.status()
    assert st["laggards"] == []
    final, pending = svc.aligned_results()
    merged = dict(final)
    merged.update(pending)
    _assert_same_results(merged, svc.results())
    assert saw_pending and final


def test_aligner_monotone_and_validates():
    al = WatermarkAligner(2, align_every=10, max_lag_epochs=1)
    with pytest.raises(ValueError):
        al.update(type("S", (), {"shard": 5, "watermark": 0,
                                 "sealed_end": 0, "processed_end": 0})())
    assert al.aligned_epoch == 0


# ------------------------------------------------------- global admission


def test_admission_modes_exposed():
    assert set(ADMISSION_MODES) == {"none", "global_fixed", "per_shard"}
    with pytest.raises(ValueError):
        ShardServiceConfig(admission="bogus")
    with pytest.raises(ValueError):
        ShardServiceConfig(n_shards=0)
    with pytest.raises(ValueError):
        ShardServiceConfig(skew=-1)


def test_per_shard_admission_sheds_under_pressure():
    """per_shard mode: the router sheds each shard's sub-chunk at that
    shard's PID state; shards themselves never shed (actuation is fully
    hoisted), and the certificate still merges to one global report."""
    wl, stream = _dataset("smarthome")
    svc = ShardedHamletService(wl, _cfg(
        2, admission="per_shard",
        overload=OverloadConfig(shed_policy="drop_tail", slo_ms=0.05,
                                micro_batch=1)))
    # shards observe latency but the router owns actuation
    assert svc._shard_overload_cfg().shed_policy == "none"
    for w in svc.workers:
        assert w.rt.shedder is None
    res = svc.run(stream)
    summ = svc.admission.summary()
    assert summ["mode"] == "per_shard"
    assert summ["offered"] == len(stream)
    assert summ["admitted"] <= summ["offered"]
    assert summ["shed"] == summ["offered"] - summ["admitted"]
    assert summ["shed"] > 0, "sub-ms SLO must force the PID to shed"
    rep = svc.error_report()
    assert rep and all(hasattr(r, "subset_guarantee") for r in rep.values())
    assert res


def test_accountant_merge_cell_exact():
    """ErrorAccountant.merged is a cell-exact union: counts sum, the
    witness bit ANDs, and window bounds match a single accountant that saw
    every shed event."""
    wl, stream = _dataset("stock")
    half = len(stream) // 2
    a_full = ErrorAccountant(wl)
    a1, a2 = ErrorAccountant(wl), ErrorAccountant(wl)
    lo = stream.select(np.arange(half))
    hi = stream.select(np.arange(half, len(stream)))
    a_full.record(lo, witnessed=True)
    a_full.record(hi, witnessed=False, late=True)
    a1.record(lo, witnessed=True)
    a2.record(hi, witnessed=False, late=True)
    merged = ErrorAccountant.merged([a1, a2])
    assert merged.total_shed == a_full.total_shed == len(stream)
    assert merged.late_events == a_full.late_events == len(hi)
    assert merged._shed == a_full._shed
    assert merged.report() == a_full.report()
    q = wl.atomic[0]
    g = int(stream.group[0])
    assert merged.window_bound(q.name, g, 0) == \
        a_full.window_bound(q.name, g, 0)


def test_accountant_merge_rejects_pane_mismatch():
    wl, _ = _dataset("stock")
    a1 = ErrorAccountant(wl, pane=5)
    a2 = ErrorAccountant(wl, pane=10)
    with pytest.raises(ValueError):
        ErrorAccountant.merged([a1, a2])
    with pytest.raises(ValueError):
        ErrorAccountant.merged([])


def test_merge_error_reports_sums_and_conjoins():
    wl, stream = _dataset("stock")
    a1, a2 = ErrorAccountant(wl), ErrorAccountant(wl)
    a1.record(stream.select(np.arange(len(stream) // 2)), witnessed=True)
    a2.record(stream.select(np.arange(len(stream) // 2, len(stream))))
    r1, r2 = a1.report(), a2.report()
    fleet = merge_error_reports([r1, r2])
    for name, r in fleet.items():
        assert r.shed_kleene == r1[name].shed_kleene + r2[name].shed_kleene
        assert r.cells_affected == (r1[name].cells_affected
                                    + r2[name].cells_affected)
        assert r.subset_guarantee == (r1[name].subset_guarantee
                                      and r2[name].subset_guarantee)


# ----------------------------------------------------------- placement


def test_placement_deterministic_and_balanced():
    assert ring_hash("g:42") == ring_hash("g:42")
    assert ring_hash("g:42") != ring_hash("g:43")
    pt1 = PlacementTable(4, groups_per_tenant=2)
    pt2 = PlacementTable(4, groups_per_tenant=2)
    groups = np.arange(200)
    assert np.array_equal(pt1.shard_of_groups(groups),
                          pt2.shard_of_groups(groups))
    assert [pt1.shard_of(g) for g in groups.tolist()] == \
        pt1.shard_of_groups(groups).tolist()
    # every shard owns someone; same-tenant groups colocate
    owned = {pt1.shard_of(g) for g in range(200)}
    assert owned == set(range(4))
    for g in range(0, 200, 2):
        assert pt1.shard_of(g) == pt1.shard_of(g + 1)


def test_placement_partition_and_overrides():
    pt = PlacementTable(3)
    groups = list(range(30))
    on = [pt.groups_on(s, groups) for s in range(3)]
    assert sorted(g for part in on for g in part) == groups
    g = 7
    before = pt.shard_of(g)
    target = (before + 1) % 3
    v0 = pt.version
    pt.override(g, target)
    assert pt.shard_of(g) == target and pt.version == v0 + 1
    assert pt.shard_of_groups(np.array([g]))[0] == target
    pt.clear_override(g)
    assert pt.shard_of(g) == before


# --------------------------------------------------- merged observability


def test_runstats_merge_parity_and_counts():
    """Fleet RunStats: count fields are shard-count invariant (merged
    4-shard == 1-shard), wall timers sum rather than match."""
    wl, stream = _dataset("ridesharing")
    svcs = {n: ShardedHamletService(wl, _cfg(n)) for n in (1, 4)}
    for svc in svcs.values():
        svc.run(stream)
    s1, s4 = svcs[1].stats(), svcs[4].stats()
    assert s1.counts() == s4.counts()
    assert 0 < s1.events <= len(stream)
    for f in RunStats.COUNT_FIELDS:
        assert f in s1.counts()
    assert "plan_cache_hits" not in RunStats.COUNT_FIELDS


def test_runstats_merged_sums_parts():
    a, b = RunStats(), RunStats()
    a.events, b.events = 3, 4
    a.plan_s, b.plan_s = 0.5, 0.25
    m = RunStats.merged([a, b])
    assert m.events == 7 and m.plan_s == 0.75


def test_observability_merge_across_shards():
    """collect() with per-shard observability merges the registries:
    every merged counter equals the sum over shards, histograms keep
    their total counts."""
    wl, stream = _dataset("ridesharing")
    svc = ShardedHamletService(wl, _cfg(2, obs=True))
    svc.run(stream)
    out = svc.collect()
    merged = out["metrics"]
    shards = out["shard_metrics"]
    assert merged, "registry-only observability must collect series"
    hists = [n for n, v in merged.items()
             if isinstance(v, dict) and "count" in v]
    assert hists, "phase histograms must be recorded"
    for name in hists:
        assert merged[name]["count"] == sum(
            s[name]["count"] for s in shards if name in s), name
    for s in shards:          # every shard series appears in the merge
        assert set(s) <= set(merged)
