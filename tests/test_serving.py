"""Serving front-end: session determinism contract, continuous batching,
retraction-channel delivery, concurrent-producer ingress safety, parallel
shard drive parity, pipelined flush parity, and lifecycle hygiene."""

import queue
import threading

import numpy as np
import pytest

from repro.core.engine import HamletRuntime, vals_equal
from repro.core.events import EventBatch
from repro.core.pattern import And, EventType, Kleene, Or, Seq
from repro.core.query import Query, Workload
from repro.eventtime.config import EventTimeConfig
from repro.overload.config import OverloadConfig
from repro.overload.ingress import IngressQueue
from repro.overload.runtime import OverloadRuntime
from repro.serve import ContinuousBatcher, ServingFrontend
from repro.shardsvc import (ShardedHamletService, ShardServiceConfig,
                            WatermarkAligner)
from repro.eventtime.frontier import FrontierSnapshot
from repro.streams.generator import (NAMED_STREAMS, RIDESHARING_SCHEMA,
                                     SMARTHOME_SCHEMA, STOCK_SCHEMA,
                                     TAXI_SCHEMA, DisorderConfig,
                                     apply_disorder)

DATASETS = {
    "ridesharing": (RIDESHARING_SCHEMA, "Travel", ("Request", "Accept")),
    "stock": (STOCK_SCHEMA, "Quote", ("Buy", "Sell")),
    "smarthome": (SMARTHOME_SCHEMA, "Measure", ("Load", "Work")),
    "taxi": (TAXI_SCHEMA, "Travel", ("Request", "Pickup")),
}

STREAM_KW = {"ridesharing": dict(events_per_minute=250, minutes=1,
                                 n_groups=6),
             "stock": dict(events_per_minute=300, minutes=1, n_groups=6),
             "smarthome": dict(events_per_minute=300, minutes=1,
                               n_groups=6),
             "taxi": dict(events_per_minute=250, minutes=1, n_groups=6)}


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


def _by_tenant(stream, n_tenants, groups_per_tenant=2):
    parts = []
    for t in range(n_tenants):
        lo, hi = t * groups_per_tenant, (t + 1) * groups_per_tenant
        mask = (stream.group >= lo) & (stream.group < hi)
        parts.append(stream.select(np.flatnonzero(mask)))
    return parts


def _trickle(fe, parts, seed, chunk=40, pump_p=0.5, groups=None):
    """Random seeded interleaving: sessions submit chunks in shuffled
    order, pumping stochastically along the way."""
    rng = np.random.default_rng(seed)
    sessions = [fe.open_session(tenant=t, groups=groups)
                for t in range(len(parts))]
    cursors = [0] * len(parts)
    while any(c < len(p) for c, p in zip(cursors, parts)):
        t = int(rng.integers(0, len(parts)))
        if cursors[t] >= len(parts[t]):
            continue
        c0 = cursors[t]
        c1 = min(c0 + chunk, len(parts[t]))
        sessions[t].submit(parts[t].select(np.arange(c0, c1)))
        cursors[t] = c1
        if rng.random() < pump_p:
            fe.pump()
    for s in sessions:
        s.close()
    return sessions


def _assert_same(a, b, ctx=""):
    assert set(a) == set(b), ctx
    for k in a:
        assert vals_equal(a[k], b[k]), (ctx, k)


# ------------------------------------------------- determinism contract


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_serving_determinism_sweep(name):
    """For any interleaving of session submissions the drained results are
    bitwise equal to the single-threaded epoch-synchronous run of the
    merged stream — 3 seeded schedules per dataset."""
    wl, stream = _dataset(name)
    ref = OverloadRuntime(
        wl, OverloadConfig(shed_policy="none", micro_batch=4)).run(stream)
    parts = _by_tenant(stream, 3)
    for seed in (0, 1, 2):
        fe = ServingFrontend(
            wl, backend="overload",
            overload=OverloadConfig(shed_policy="none", micro_batch=4),
            groups_per_tenant=2)
        _trickle(fe, parts, seed)
        _assert_same(fe.drain(), ref, (name, seed))


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_serving_eventtime_disorder_determinism(name):
    """Event-time backend: sessions receive a *disordered* arrival split
    (stragglers violate the serving watermark), revision repairs them, and
    final results still match the in-order batch run for every seeded
    interleaving."""
    wl, stream = _dataset(name)
    t_end = ((int(stream.time.max()) // 10) + 1) * 10
    ref = HamletRuntime(wl).run(stream, t_end=t_end)
    ds = apply_disorder(stream, DisorderConfig(fraction=0.3, max_skew=6,
                                               seed=5))
    base = ds.base                       # seq = producer (true) order
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        fe = ServingFrontend(wl, backend="eventtime",
                             eventtime=EventTimeConfig(skew=8),
                             micro_batch=2, skew=8, groups_per_tenant=2)
        sessions = [fe.open_session(tenant=t) for t in range(3)]
        # deal the arrival sequence out in randomly sized chunks to random
        # sessions; chunk-local sort restores the per-session time order
        # the submit contract requires, cross-session disorder remains and
        # producer seq rides through so timestamp ties keep trace order
        cur = 0
        while cur < len(base):
            n = int(rng.integers(20, 60))
            idx = ds.order[cur:min(cur + n, len(base))]
            sub = EventBatch.from_unsorted(
                base.schema, base.type_id[idx], base.time[idx],
                base.attrs[idx], base.group[idx], seq=base.seq[idx])
            sessions[int(rng.integers(0, 3))].submit(sub)
            cur += n
            if rng.random() < 0.5:
                fe.pump()
        for s in sessions:
            s.close()
        fe.drain()
        got = {k: v for k, v in fe.results().items() if k in ref}
        _assert_same(got, ref, (name, seed))


def test_session_ordering_per_group():
    """One session's channel sees each (query, group) window exactly once,
    in nondecreasing w0 order, and only for groups it subscribes to."""
    wl, stream = _dataset("ridesharing")
    fe = ServingFrontend(
        wl, backend="overload",
        overload=OverloadConfig(shed_policy="none", micro_batch=2),
        groups_per_tenant=2)
    sessions = _trickle(fe, _by_tenant(stream, 3), seed=3)
    fe.drain()
    total = 0
    for t, s in enumerate(sessions):
        seen_w0 = {}
        for d in s.poll():
            assert d.kind == "emit"
            assert d.group // 2 == t, "delivery routed to wrong tenant"
            seen_w0.setdefault((d.query, d.group), []).append(d.w0)
            total += 1
        for key, w0s in seen_w0.items():
            assert w0s == sorted(w0s), key
            assert len(set(w0s)) == len(w0s), key
        assert s.drained
    assert total == len(fe.results())


def _or_and_workload():
    """The ridesharing workload plus an Or and an And query, each over
    two atomic parts with disjoint types."""
    wl, _ = _dataset("ridesharing")
    req, acc, travel = (EventType(t) for t in ("Request", "Accept",
                                               "Travel"))
    return Workload(wl.schema, list(wl.queries) + [
        Query("qor", Or(Kleene(travel), Kleene(req)), within=20, slide=10),
        Query("qand", And(Seq(acc, Kleene(travel)), Kleene(req)),
              within=20, slide=10)])


_MB4 = OverloadConfig(shed_policy="none", micro_batch=4)
_EMISSION_CASES = {
    "overload": ({"backend": "overload", "overload": _MB4}, False),
    "overload-or-and": ({"backend": "overload", "overload": _MB4}, True),
    **{f"sharded-{mode}": ({"backend": "sharded",
                            "shard_cfg": ShardServiceConfig(
                                n_shards=2, admission="none",
                                parallel=parallel, overload=_MB4)}, False)
       for mode, parallel in (("serial", False), ("thread", "thread"),
                              ("process", "process"))},
}


@pytest.mark.parametrize("case", sorted(_EMISSION_CASES))
def test_runtime_records_deliver_each_window_once(case):
    """Every backend hands the front-end the windows it closed: each
    session subscribed to everything receives every drained result
    exactly once, as a bitwise-equal ``emit`` — never a retract or an
    amend — in nondecreasing ``w0`` per (query, group)."""
    kw, or_and = _EMISSION_CASES[case]
    wl, stream = _dataset("ridesharing")
    if or_and:
        wl = _or_and_workload()
    fe = ServingFrontend(wl, groups_per_tenant=2, **kw)
    sessions = _trickle(fe, _by_tenant(stream, 3), seed=7, groups="all")
    res = fe.drain()
    assert res
    for s in sessions:
        got, w0s = {}, {}
        for d in s.poll():
            assert d.kind == "emit" and d.revision == 0, d
            key = (d.query, d.group, d.w0)
            assert key not in got, ("delivered twice", key)
            got[key] = d.vals
            w0s.setdefault(key[:2], []).append(d.w0)
        _assert_same(got, res, case)
        for qg, seq in w0s.items():
            assert seq == sorted(seq), qg


@pytest.mark.parametrize("backend", ["overload", "sharded"])
def test_pump_never_reads_backend_results(backend):
    """Routing a flush costs the windows it closed, not the run's: no pump
    rebuilds ``results()``; only ``drain`` reads them, once."""
    kw, _ = _EMISSION_CASES["overload" if backend == "overload"
                            else "sharded-serial"]
    wl, stream = _dataset("ridesharing")
    fe = ServingFrontend(wl, groups_per_tenant=2, **kw)
    calls = []
    results = fe._backend.results
    fe._backend.results = lambda: calls.append(1) or results()
    _trickle(fe, _by_tenant(stream, 3), seed=4, pump_p=1.0)
    assert fe.pump_cycles > 0 and fe.deliveries > 0
    assert calls == []
    fe.drain()
    assert calls == [1]


def test_retraction_channel_delivery():
    """A straggler that lands in an already-emitted window produces a
    retract + amend pair on exactly the subscribing session's channel,
    with the revision counter stepping."""
    schema, kleene, heads = DATASETS["ridesharing"]
    wl = _wl(schema, kleene, heads)
    stream = NAMED_STREAMS["ridesharing"](events_per_minute=250, minutes=1,
                                          n_groups=4)
    fe = ServingFrontend(wl, backend="eventtime",
                         eventtime=EventTimeConfig(skew=4, speculative=True),
                         skew=0, groups_per_tenant=2)
    s0 = fe.open_session(tenant=0)
    s1 = fe.open_session(tenant=1)
    g0 = stream.select(np.flatnonzero(stream.group < 2))
    g1 = stream.select(np.flatnonzero(stream.group >= 2))
    # tenant 1 submits everything up front; tenant 0 holds one early burst
    # back until the window has long been speculatively emitted
    late_n = 8
    s1.submit(g1)
    s0.submit(g0.select(np.arange(late_n, len(g0))))
    fe.pump()
    straggler = g0.select(np.arange(late_n))
    s0.submit(straggler)
    s0.close()
    s1.close()
    fe.drain()
    d0, d1 = s0.poll(), s1.poll()
    assert all(d.group < 2 for d in d0)
    assert all(d.group >= 2 for d in d1)
    kinds0 = {d.kind for d in d0}
    assert "retract" in kinds0 and "amend" in kinds0, \
        "straggler must revise an emitted window on the subscriber channel"
    assert not any(d.kind == "retract" for d in d1), \
        "revision leaked to a non-subscribing session"
    # retract/amend pairing and revision stepping per window key
    by_key = {}
    for d in d0:
        by_key.setdefault((d.query, d.group, d.w0), []).append(d)
    for key, ds in by_key.items():
        revs = [d.revision for d in ds if d.kind != "retract"]
        assert revs == sorted(revs), key
        for i, d in enumerate(ds):
            if d.kind == "amend":
                assert i > 0 and ds[i - 1].kind == "retract", key
                assert d.vals is not None
                assert not vals_equal(ds[i - 1].vals, d.vals), \
                    "amend must replace the withdrawn value with a new one"


# ------------------------------------------------- continuous batching


def test_continuous_batcher_watermark_and_seal():
    wl, _ = _dataset("ridesharing")
    cb = ContinuousBatcher(wl.schema, pane=10, skew=0)
    cb.track(0)
    cb.track(1)
    t = np.arange(25, dtype=np.int64)
    b = EventBatch(wl.schema, np.zeros(25, np.int32), t,
                   np.zeros((25, len(wl.schema.attrs)), np.float64),
                   np.zeros(25, np.int64), seq=t)
    cb.stage(0, b)
    # session 1 silent at 0: nothing seals
    assert cb.watermark() == 0
    assert cb.seal() == (None, 0)
    cb.advance(1, 18)
    chunk, boundary = cb.seal()
    assert boundary == 10 and len(chunk) == 10
    cb.release(1)           # closed: only session 0's frontier (24) holds
    chunk, boundary = cb.seal()
    assert boundary == 20 and len(chunk) == 10
    assert cb.sealed_events == 20 and len(cb) == 5
    cb.release(0)           # all closed: the watermark HOLDS (only an
    assert cb.watermark() == 20  # explicit drain finalizes the tail)
    assert cb.seal() == (None, 20)


def test_session_opening_after_all_others_closed_keeps_its_stream():
    """A transient empty session set must not finalize: a wire client can
    connect a moment after an earlier client already submitted, closed,
    and had its panes pumped.  The late session's (time-overlapping)
    stream must still produce its windows rather than being pre-sealed
    into straggler territory."""
    wl, stream = _dataset("ridesharing")
    gpt = 3
    fe = ServingFrontend(
        wl, backend="overload",
        overload=OverloadConfig(shed_policy="none", micro_batch=4),
        groups_per_tenant=gpt)
    p0, p1 = _by_tenant(stream, 2, groups_per_tenant=gpt)
    hi = int(stream.time.max()) + 1
    sA = fe.open_session(tenant=1)
    sA.submit(p1)
    sA.advance_to(hi)
    sA.close()
    fe.pump()               # empty session set: pump must hold, not seal
    sB = fe.open_session(tenant=0)
    sB.submit(p0)
    sB.advance_to(hi)
    sB.close()
    res = fe.drain()
    ref = OverloadRuntime(
        wl, OverloadConfig(shed_policy="none", micro_batch=4)).run(stream)
    _assert_same(res, ref, "late-opening session")
    got_b = [d for d in sB.poll() if d.kind != "retract"]
    assert got_b and all(d.group < gpt for d in got_b)


def test_submission_split_inside_a_tick_keeps_its_tail():
    """A submission promises no event earlier than its last tick, not
    past it: the next one may carry more events of that tick.  A pump
    between the two, with the other sessions ahead, must not seal that
    tick's pane and drop the tail as late."""
    wl, stream = _dataset("taxi")
    cfg = OverloadConfig(shed_policy="none", micro_batch=4)
    fe = ServingFrontend(wl, backend="overload", overload=cfg,
                         groups_per_tenant=2)
    parts = _by_tenant(stream, 3)
    sessions = [fe.open_session(tenant=t) for t in range(3)]
    p0, t = parts[0], parts[0].time
    cut = next(i for i in range(1, len(t))
               if t[i] == t[i - 1] and (t[i] + 1) % fe.pane == 0)
    for s, p in zip(sessions[1:], parts[1:]):
        s.submit(p)
    sessions[0].submit(p0.select(np.arange(cut)))
    fe.pump()
    sessions[0].submit(p0.select(np.arange(cut, len(p0))))
    for s in sessions:
        s.close()
    _assert_same(fe.drain(), OverloadRuntime(wl, cfg).run(stream),
                 "a tick split across submissions")


def test_sessions_fill_shared_microbatches():
    """Concurrent trickles land in the same K-pane fused flushes: the
    engine sees the same number of micro-batch flushes as the one-stream
    batch run, not one flush per session."""
    wl, stream = _dataset("ridesharing")
    K = 4
    ref_rt = OverloadRuntime(wl, OverloadConfig(shed_policy="none",
                                                micro_batch=K))
    ref_rt.run(stream)
    ref_flushes = ref_rt.rt.executor.flushes
    fe = ServingFrontend(
        wl, backend="overload",
        overload=OverloadConfig(shed_policy="none", micro_batch=K),
        groups_per_tenant=2)
    _trickle(fe, _by_tenant(stream, 3), seed=1, chunk=25, pump_p=0.8)
    fe.drain()
    srv_rt = fe._backend.rt
    assert srv_rt.metrics.summary()["panes"] == \
        ref_rt.metrics.summary()["panes"]
    assert srv_rt.rt.executor.flushes == pytest.approx(ref_flushes, abs=2)


def test_session_admission_sheds_at_the_door():
    wl, stream = _dataset("ridesharing")
    fe = ServingFrontend(
        wl, backend="overload",
        overload=OverloadConfig(shed_policy="drop_tail", fixed_shed=0.5),
        groups_per_tenant=2, session_admission=True)
    s = fe.open_session(tenant=0, groups="all")
    accepted = s.submit(stream)
    assert accepted == pytest.approx(len(stream) * 0.5, rel=0.01)
    fe.drain()
    summ = fe.summary()
    assert summ["session_shed"] == len(stream) - accepted
    assert summ["sessions"][0]["shed"] == summ["session_shed"]


# ------------------------------------------------- ingress under threads


def test_ingress_queue_concurrent_producers_stress():
    """Many producer threads offering into one IngressQueue: no event is
    lost or duplicated (accepted == drained), no crash, capacity respected."""
    wl, stream = _dataset("ridesharing")
    q = IngressQueue(wl.schema, capacity=1 << 20)
    n_threads, per_thread = 8, 30
    rng = np.random.default_rng(0)
    cuts = np.sort(rng.choice(np.arange(1, len(stream)),
                              n_threads * per_thread - 1, replace=False))
    subs = [stream.select(np.arange(a, b))
            for a, b in zip(np.r_[0, cuts], np.r_[cuts, len(stream)])]
    accepted = [0] * n_threads
    barrier = threading.Barrier(n_threads)

    def produce(i):
        barrier.wait()
        for sub in subs[i::n_threads]:
            accepted[i] += q.offer(sub)

    threads = [threading.Thread(target=produce, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(accepted) == len(stream)
    drained = q.poll_until(int(stream.time.max()) + 1)
    assert len(drained) == len(stream)
    # multiset of (time, type) pairs survives the concurrent merge
    want = sorted(zip(stream.time.tolist(), stream.type_id.tolist()))
    got = sorted(zip(drained.time.tolist(), drained.type_id.tolist()))
    assert got == want


# ------------------------------------------------- parallel shard drive


def test_parallel_shard_drive_bitwise_parity():
    """parallel=True drives shards on a thread pool through the rendezvous
    aligner; results and aligned epochs match the serial drive bitwise."""
    wl, stream = _dataset("stock")
    runs = {}
    for parallel in (False, True):
        cfg = ShardServiceConfig(
            n_shards=4, admission="none", parallel=parallel,
            overload=OverloadConfig(shed_policy="none", micro_batch=4))
        svc = ShardedHamletService(wl, cfg)
        runs[parallel] = (svc.run(stream, chunk_ticks=10),
                          svc.aligner.aligned_epoch)
        assert svc.drive_cycles > 0
        if parallel:
            assert svc.drive_wall_s > 0.0
    _assert_same(runs[False][0], runs[True][0])
    assert runs[False][1] == runs[True][1]


def test_aligner_rendezvous_blocks_until_all_arrive():
    al = WatermarkAligner(3, align_every=10)
    out = {}

    def arrive(s, wm):
        out[s] = al.arrive(FrontierSnapshot(shard=s, watermark=wm,
                                            sealed_end=wm, processed_end=wm))

    threads = [threading.Thread(target=arrive, args=(s, 20 + s))
               for s in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=0.2)
    assert all(t.is_alive() for t in threads), \
        "rendezvous released before the last shard arrived"
    arrive(2, 25)
    for t in threads:
        t.join(timeout=5.0)
    assert set(out) == {0, 1, 2}
    assert len({v for v in out.values()}) == 1, "shards saw different epochs"
    assert out[0] == 2          # min watermark 20 // align_every 10


def test_serving_sharded_backend_matches_single():
    wl, stream = _dataset("taxi")
    ref = OverloadRuntime(
        wl, OverloadConfig(shed_policy="none", micro_batch=4)).run(stream)
    cfg = ShardServiceConfig(
        n_shards=2, admission="none", parallel=True,
        overload=OverloadConfig(shed_policy="none", micro_batch=4))
    fe = ServingFrontend(wl, backend="sharded", shard_cfg=cfg,
                         groups_per_tenant=2)
    _trickle(fe, _by_tenant(stream, 3), seed=2)
    _assert_same(fe.drain(), ref)


# ------------------------------------------------- pipelined flush


def test_pipelined_flush_bitwise_parity():
    wl, stream = _dataset("smarthome")
    runs = {}
    for pipelined in (False, True):
        rt = OverloadRuntime(wl, OverloadConfig(
            shed_policy="none", micro_batch=4, pipeline_flush=pipelined))
        runs[pipelined] = rt.run(stream)
        rt.shutdown()
    _assert_same(runs[False], runs[True])


# ------------------------------------------------- async consumption


def test_async_stream_iterator_delivers_everything():
    import asyncio

    wl, stream = _dataset("ridesharing")
    fe = ServingFrontend(
        wl, backend="overload",
        overload=OverloadConfig(shed_policy="none", micro_batch=2),
        groups_per_tenant=2)
    s = fe.open_session(tenant=0, groups="all")

    async def consume():
        return [d async for d in s.stream()]

    async def main():
        task = asyncio.ensure_future(consume())
        loop = asyncio.get_running_loop()

        def feed():
            fe.start(interval_s=0.001)
            for t0 in range(0, int(stream.time.max()) + 1, 15):
                s.submit(stream.time_slice(t0, t0 + 15))
            s.close()
            fe.drain()

        await loop.run_in_executor(None, feed)
        return await task

    got = asyncio.run(main())
    assert len(got) == len(fe.results())
    assert s.drained


# ------------------------------------------------- lifecycle hygiene


def test_no_leaked_threads_after_drain():
    before = set(threading.enumerate())
    wl, stream = _dataset("ridesharing")
    fe = ServingFrontend(
        wl, backend="sharded",
        shard_cfg=ShardServiceConfig(
            n_shards=2, admission="none", parallel=True,
            overload=OverloadConfig(shed_policy="none", micro_batch=2,
                                    pipeline_flush=True)),
        groups_per_tenant=2)
    fe.start(interval_s=0.001)
    sessions = _trickle(fe, _by_tenant(stream, 3), seed=0, pump_p=0.0)
    fe.drain()
    for s in sessions:
        s.poll()
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()
              and "ThreadPoolExecutor" not in repr(t)
              and "asyncio" not in t.name]
    assert not leaked, leaked


def test_prefetch_iterator_close_joins_producer():
    from repro.train.data import PrefetchIterator, SyntheticLM

    before = {t for t in threading.enumerate()}
    with PrefetchIterator(SyntheticLM(64, 2, 8), depth=2) as it:
        next(it)
    after = [t for t in threading.enumerate()
             if t not in before and t.is_alive()]
    assert not after, "producer thread survived close()"


def test_checkpoint_manager_close_joins_async_write(tmp_path):
    jnp = pytest.importorskip("jax.numpy")
    from repro.distributed.checkpoint import CheckpointManager, latest_step

    with CheckpointManager(str(tmp_path), interval=1, keep=2) as mgr:
        mgr.maybe_save(1, {"x": jnp.zeros((128,))})
    assert latest_step(str(tmp_path)) == 1
    assert not any(t.name == "ckpt-write" for t in threading.enumerate()
                   if t.is_alive())


# ------------------------------------------------- observability surface


def test_serving_latency_surfaced_in_collect():
    from repro.obs import Observability

    wl, stream = _dataset("ridesharing")
    obs = Observability()
    fe = ServingFrontend(
        wl, backend="overload",
        overload=OverloadConfig(shed_policy="none", micro_batch=2),
        groups_per_tenant=2, obs=obs)
    _trickle(fe, _by_tenant(stream, 3), seed=0)
    fe.drain()
    out = obs.collect(serving=fe)
    srv = out["serving"]
    assert srv["deliveries"] > 0
    assert srv["latency_ms"]["n"] == srv["deliveries"]
    for sid, sess in srv["sessions"].items():
        if sess["delivered"]:
            assert sess["p99_ms"] >= sess["p50_ms"] >= 0.0
    assert srv["tenants"], "per-tenant latency series missing"
    # registry side: counters + shared latency histogram populated
    assert out["metrics"]["serve.deliveries"] == srv["deliveries"]
    assert out["metrics"]["serve.submitted"] == len(stream)
    assert out["metrics"]["serve.latency_ms"]["count"] == srv["deliveries"]
    # serve.flush spans landed on the trace
    names = {e["name"] for e in obs.tracer.events()}
    assert "serve.flush" in names
