"""Process-pool shard drive: bitwise parity with the serial drive (ordered
and event-time disordered arrivals), chunk shipping codec, lifecycle
hygiene (no lingering worker processes), and mode plumbing."""

import multiprocessing as mp
import threading

import numpy as np
import pytest

from repro.core.engine import vals_equal
from repro.core.pattern import EventType, Kleene, Seq
from repro.core.query import Query, Workload
from repro.overload import OverloadConfig
from repro.shardsvc import (ProcShardWorker, ShardedHamletService,
                            ShardServiceConfig)
from repro.shardsvc.procdrive import (INLINE_BYTES, _pack_columns,
                                      _unpack_columns)
from repro.streams.generator import (NAMED_STREAMS, STOCK_SCHEMA,
                                     TAXI_SCHEMA, DisorderConfig,
                                     apply_disorder)

pytestmark = pytest.mark.slow     # spawn start-up dominates on small hosts


def _wl(schema, kleene, heads, within=20, slide=10):
    k = EventType(kleene)
    qs = [Query(f"q{i}", Seq(EventType(h), Kleene(k)),
                within=within, slide=slide)
          for i, h in enumerate(heads)]
    qs.append(Query("qk", Kleene(k), within=within, slide=slide))
    return Workload(schema, qs)


def _stock():
    return (_wl(STOCK_SCHEMA, "Quote", ("Buy", "Sell")),
            NAMED_STREAMS["stock"](events_per_minute=300, minutes=1,
                                   n_groups=6))


def _cfg(n_shards, **kw):
    kw.setdefault("admission", "none")
    kw.setdefault("overload",
                  OverloadConfig(shed_policy="none", micro_batch=4))
    return ShardServiceConfig(n_shards=n_shards, **kw)


def _assert_same(a, b, ctx=""):
    assert set(a) == set(b), ctx
    for k in a:
        assert vals_equal(a[k], b[k]), (ctx, k)


# ---------------------------------------------------------------- parity


def test_process_drive_bitwise_parity_and_read_side():
    """parallel="process" pins each shard in a spawn process; results,
    aligned epochs and fleet stats match the serial drive bitwise, and the
    whole post-close read side still answers after the workers exited."""
    wl, stream = _stock()
    runs, epochs, counts = {}, {}, {}
    for parallel in (False, "process"):
        svc = ShardedHamletService(wl, _cfg(4, parallel=parallel))
        runs[parallel] = svc.run(stream, chunk_ticks=10)
        epochs[parallel] = svc.aligner.aligned_epoch
        counts[parallel] = svc.stats().counts()
        assert svc.drive_cycles > 0
        if parallel == "process":
            assert svc.drive_wall_s > 0.0
            # post-close reads served from the shutdown snapshot
            assert svc.error_report() is not None
            out = svc.collect()
            assert out["router"]["drive_mode"] == "process"
            assert all("process" in s for s in out["shards"])
            with pytest.raises(RuntimeError):
                svc.workers[0]._rpc("cycle", None, 0, None)
    _assert_same(runs[False], runs["process"])
    assert epochs[False] == epochs["process"]
    assert counts[False] == counts["process"]
    assert runs[False], "parity is vacuous without results"
    assert not mp.active_children(), "worker processes leaked past close()"


def test_process_drive_eventtime_disorder_parity():
    """Disordered arrival through per-shard reorder buffers inside worker
    processes: results and late accounting match the serial drive."""
    wl = _wl(TAXI_SCHEMA, "Travel", ("Request", "Pickup"))
    stream = NAMED_STREAMS["taxi"](events_per_minute=250, minutes=1,
                                   n_groups=6)
    ds = apply_disorder(stream, DisorderConfig(
        model="bounded_skew", fraction=0.2, max_skew=6, seed=5))
    runs, lost = {}, {}
    for parallel in (False, "process"):
        svc = ShardedHamletService(
            wl, _cfg(2, parallel=parallel, eventtime=True,
                     skew=ds.max_lateness()))
        runs[parallel] = svc.run_chunks(ds.chunks(64))
        lost[parallel] = (sum(w.late_total for w in svc.workers),
                          sum(w.expired_total for w in svc.workers))
    _assert_same(runs[False], runs["process"])
    assert lost[False] == lost["process"] == (0, 0)
    assert not mp.active_children()


# ----------------------------------------------------------- chunk codec


def test_column_codec_roundtrip_inline_and_shm_sizes():
    wl, stream = _stock()
    for n in (0, 3, len(stream)):
        sub = stream.select(np.arange(n))
        payload = _pack_columns(sub)
        back = _unpack_columns(wl.schema, payload)
        assert np.array_equal(back.type_id, sub.type_id)
        assert np.array_equal(back.time, sub.time)
        assert np.array_equal(back.attrs, sub.attrs)
        assert np.array_equal(back.group, sub.group)
        if sub.seq is not None:
            assert np.array_equal(back.seq, sub.seq)
    # a large batch crosses the inline threshold; ship it through an
    # actual shared-memory segment and load it back the way a child does
    big = stream.select(
        np.repeat(np.arange(len(stream)), 1 + INLINE_BYTES // 1000))
    payload = _pack_columns(big)
    assert len(payload) > INLINE_BYTES
    from multiprocessing import shared_memory

    from repro.shardsvc.procdrive import _load_chunk
    seg = shared_memory.SharedMemory(create=True, size=len(payload))
    try:
        seg.buf[:len(payload)] = payload
        back = _load_chunk(wl.schema, {"shm": seg.name,
                                       "size": len(payload)})
    finally:
        seg.close()
        seg.unlink()
    assert np.array_equal(back.time, big.time)
    assert np.array_equal(back.attrs, big.attrs)


# ------------------------------------------------------------- lifecycle


def test_process_worker_shutdown_is_idempotent_and_clean():
    wl, _ = _stock()
    before = set(threading.enumerate())
    w = ProcShardWorker(0, wl, OverloadConfig(shed_policy="none",
                                              micro_batch=4))
    w.wait_ready()
    assert w.pane > 0
    w.close(0)
    w.shutdown()
    w.shutdown()                      # second call is a no-op
    assert w.results() == {}          # snapshot survives the process
    assert w.take_emitted() == []      # no record outlives the process
    assert not mp.active_children()
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked, leaked


def test_process_mode_rejects_rebalance():
    wl, stream = _stock()
    svc = ShardedHamletService(wl, _cfg(2, parallel="process"))
    try:
        svc.ingest(stream.time_slice(0, 10))
        with pytest.raises(NotImplementedError):
            svc.plan_rebalance(group=0, to_shard=1)
    finally:
        svc.close()
    assert not mp.active_children()


# ------------------------------------------------------------- plumbing


def test_drive_mode_resolution_and_validation():
    assert ShardServiceConfig(parallel=False).drive_mode == "serial"
    assert ShardServiceConfig(parallel=True).drive_mode == "thread"
    assert ShardServiceConfig(parallel="thread").drive_mode == "thread"
    assert ShardServiceConfig(parallel="process").drive_mode == "process"
    with pytest.raises(ValueError):
        ShardServiceConfig(parallel="fork")
