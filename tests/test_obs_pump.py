"""Accounting of the serving pump's time: live spans as one complete event,
the pane-track table's bound, the serve / emit / compile / gc spans and
their counters, the profiler annotations on the same spans, and the cost
of all of it with tracing off (none of it is installed).

The overload controller's input is here too: a flush that builds a program
feeds it what a warm flush does, the compile time left out.
"""

import gc
import threading
import time
from glob import glob

import jax
import numpy as np
import pytest

from repro.core.engine import HamletRuntime, vals_equal
from repro.core.events import EventBatch, StreamSchema
from repro.core.pattern import EventType, Kleene, Seq
from repro.core.query import Query, Workload, count_star
from repro.obs import Observability, Tracer
from repro.obs.facade import TIME_COUNTERS
from repro.overload import OverloadConfig
from repro.overload.runtime import OverloadRuntime
from repro.serve import ContinuousBatcher, ServingFrontend
from repro.streams.generator import NAMED_STREAMS, RIDESHARING_SCHEMA

AB = StreamSchema(types=("A", "B"), attrs=("v",))


def _ab_workload(within=20, slide=10):
    return Workload(AB, [Query("q", Seq(EventType("A"), Kleene(EventType("B"))),
                               aggs=(count_star(),), within=within,
                               slide=slide)])


def _ride():
    k = EventType("Travel")
    wl = Workload(RIDESHARING_SCHEMA, [
        Query(f"q{i}", Seq(EventType(h), Kleene(k)), within=20, slide=10)
        for i, h in enumerate(("Request", "Accept"))])
    return wl, NAMED_STREAMS["ridesharing"](events_per_minute=250,
                                            minutes=1, n_groups=6)


def _serve(wl, stream, obs, step=40, pause_s=0.004):
    """Serve ``stream`` through the background pump: one session submits
    it in steps, pausing between them so the pump also waits."""
    fe = ServingFrontend(wl, backend="overload", obs=obs,
                         overload=OverloadConfig(shed_policy="none",
                                                 micro_batch=4))
    fe.start()
    s = fe.open_session(groups="all")
    for i in range(0, len(stream), step):
        s.submit(stream.select(np.arange(i, min(i + step, len(stream)))))
        time.sleep(pause_s)
    s.close()
    return fe.drain()


def _x(obs, name):
    return [e for e in obs.tracer._snapshot()
            if e[0] == "X" and e[1] == name]


# ----------------------------------------------------------------- spans


def test_span_records_one_complete_event_per_thread():
    """A live span is one ``X`` event at exit: spans of two threads that
    overlap in time each keep their own start, duration and args."""
    tr = Tracer()
    inner_started = threading.Event()
    outer_may_end = threading.Event()

    def other():
        with tr.span("b", args={"k": 2}):
            inner_started.set()
            outer_may_end.wait(5)

    th = threading.Thread(target=other)
    with tr.span("a", args={"k": 1}) as sp:
        th.start()
        inner_started.wait(5)
        time.sleep(0.01)
    outer_may_end.set()
    th.join(5)
    assert not th.is_alive()
    evs = tr._snapshot()
    assert [e[0] for e in evs] == ["X", "X"]
    a = next(e for e in evs if e[1] == "a")
    b = next(e for e in evs if e[1] == "b")
    assert a[6] == {"k": 1} and b[6] == {"k": 2}
    assert a[3] < b[3] and b[3] + b[4] > a[3] + a[4]   # they overlap
    assert a[4] == pytest.approx(sp.dur * 1e6)
    assert not hasattr(tr, "_stack")


def test_pane_track_table_is_bounded():
    """10,000 panes through a traced runtime leave the pane-key table at
    its bound; every pane still got a track of its own."""
    wl = _ab_workload(within=4, slide=2)
    obs = Observability(audit=False, capacity=1 << 12)
    rt = HamletRuntime(wl, obs=obs, micro_batch=16)
    n = 10_000
    t = np.arange(n, dtype=np.int64) * rt.pane
    b = EventBatch(AB, (np.arange(n) % 2).astype(np.int32), t,
                   np.zeros((n, 1)), np.zeros(n, np.int64))
    rt.run(b)
    tr = obs.tracer
    assert len(tr._tids) == tr.max_tracks < n
    assert tr._next_tid - 1 == n


def test_traced_serve_names_the_pump_time():
    """A traced served run records the serve, emit spans and the counters
    they sum into; the counters equal their spans' totals; results are
    those of the untraced run."""
    wl, stream = _ride()
    want = _serve(wl, stream, None)
    obs = Observability(audit=False)
    got = _serve(wl, stream, obs)
    assert got.keys() == want.keys()
    assert all(vals_equal(got[k], want[k]) for k in want)
    series = obs.registry.collect()
    for name in TIME_COUNTERS:
        assert name in series, name
    for span, counter in (("serve.seal", "serve.seal_s"),
                          ("serve.route", "serve.route_s"),
                          ("serve.wait", "serve.wait_s"),
                          ("emit", "engine.emit_s")):
        evs = _x(obs, span)
        assert evs, span
        assert sum(e[4] for e in evs) / 1e6 == pytest.approx(
            series[counter], rel=1e-9, abs=1e-12), span
    assert _x(obs, "serve.flush")
    # the retired B/E spans are complete events now, args kept
    flush = _x(obs, "flush")
    assert flush and all(e[6] == {"panes": e[6]["panes"]} for e in flush)
    evs = obs.tracer._snapshot()
    assert not [e for e in evs if e[0] in ("B", "E")]
    # the benchmark's trace window is one event of its own name
    assert "chipbench.window" not in {e[1] for e in evs}


def test_drain_routes_the_tail_inside_a_route_span():
    """What ``drain`` delivers is routed inside ``serve.route`` too, and
    counted in ``serve.route_s``: a run whose pump never routed (all of it
    staged before the drain) still names its route time."""
    wl, stream = _ride()
    obs = Observability(audit=False)
    fe = ServingFrontend(wl, backend="overload", obs=obs,
                         overload=OverloadConfig(shed_policy="none",
                                                 micro_batch=4))
    s = fe.open_session(groups="all")
    s.submit(stream)
    s.close()
    assert fe.drain()
    evs = _x(obs, "serve.route")
    assert evs
    assert sum(e[4] for e in evs) / 1e6 == pytest.approx(
        obs.registry.collect()["serve.route_s"], rel=1e-9, abs=1e-12)


def test_emit_spans_one_per_pane():
    wl, stream = _ride()
    obs = Observability(audit=False)
    rt = OverloadRuntime(wl, OverloadConfig(shed_policy="none",
                                            micro_batch=4), obs=obs)
    rt.run(stream)
    assert len(_x(obs, "emit")) == len(rt.metrics.panes)


def test_gc_and_compile_spans_and_counters():
    obs = Observability(audit=False)
    gc.collect()
    gcs = _x(obs, "gc")
    assert gcs and gcs[-1][6] == {"gen": 2}
    gc_s = obs.registry.collect()["host.gc_s"]
    assert gc_s == pytest.approx(sum(e[4] for e in gcs) / 1e6)
    # a program never built before in this process
    f = jax.jit(lambda x: x * 3.0 + float(time.perf_counter_ns() % 997))
    f(np.ones(7, np.float32)).block_until_ready()
    built = _x(obs, "compile")
    series = obs.registry.collect()
    assert built and series["kernels.programs_built"] == len(built)
    assert series["kernels.compile_s"] == pytest.approx(
        sum(e[4] for e in built) / 1e6)


def test_gc_hook_goes_with_its_observability():
    before = list(gc.callbacks)
    obs = Observability(audit=False)
    assert len(gc.callbacks) == len(before) + 1
    del obs
    gc.collect()
    assert gc.callbacks == before


def test_tracing_off_installs_nothing():
    """With ``Observability.disabled()`` a served run registers none of the
    time counters, leaves ``gc.callbacks`` as it found it, and records no
    span."""
    wl, stream = _ride()
    before = list(gc.callbacks)
    obs = Observability.disabled()
    _serve(wl, stream, obs)
    assert gc.callbacks == before
    series = obs.registry.collect()
    assert not set(TIME_COUNTERS) & set(series)
    assert len(obs.tracer) == 0
    assert "serve.session_shed" not in series
    assert "overload.shed_ratio" not in series


# ------------------------------------------------------ profiler's clock


def _host_annotations(log_dir, names):
    from jax.profiler import ProfileData

    path = sorted(glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
    return out


def test_profiler_annotations_match_ring_spans(tmp_path):
    """A profile taken around a traced served run holds the host-plane
    annotations plan, execute, finalize, emit and serve.*; each name's
    durations agree with the ring's spans (for the micro-batch regions,
    with their per-pane tiles) within 1 ms or 5 %."""
    wl, stream = _ride()
    names = ("plan", "execute", "finalize", "emit", "serve.seal",
             "serve.flush", "serve.route", "serve.wait")
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs = Observability(audit=False)
        _serve(wl, stream, obs)
    finally:
        jax.profiler.stop_trace()
    got = _host_annotations(str(tmp_path), names)
    assert set(names) <= set(got), set(names) - set(got)
    for name in names:
        ring = [e[4] / 1e6 for e in _x(obs, name)]
        ann = got[name]
        if name in ("plan", "execute", "finalize"):
            # one annotation per flush; the ring tiles it per pane
            n_flush = len(_x(obs, "flush"))
            assert len(ann) == n_flush, name
        else:
            assert len(ann) == len(ring), name
        a, r = sum(ann), sum(ring)
        assert abs(a - r) <= max(1e-3, 0.05 * r), (name, a, r)


# --------------------------------------------------------- the scheduler


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batcher_ready_agrees_with_seal(seed):
    """``ready`` says exactly when ``seal`` hands out a chunk, over random
    staging, promises and explicit boundaries."""
    rng = np.random.default_rng(seed)
    cb = ContinuousBatcher(AB, pane=10)
    for sid in range(3):
        cb.track(sid)
    t = [0, 0, 0]
    for _ in range(300):
        op = rng.integers(0, 4)
        sid = int(rng.integers(0, 3))
        if op == 0:
            n = int(rng.integers(1, 5))
            times = np.sort(t[sid] + rng.integers(-15, 12, n))
            times = np.maximum(times, 0)
            cb.stage(sid, EventBatch(AB, np.zeros(n, np.int32), times,
                                     np.zeros((n, 1)), np.zeros(n, np.int64),
                                     seq=rng.integers(0, 1 << 30, n)))
            t[sid] = int(times[-1])
        elif op == 1:
            t[sid] += int(rng.integers(0, 8))
            cb.advance(sid, t[sid])
        else:
            upto = (None if op == 2
                    else int(rng.integers(0, max(t) + 20)))
            ready = cb.ready(upto)
            chunk, _ = cb.seal(upto)
            assert ready == (chunk is not None)


# ------------------------------------------------- the overload controller


def test_controller_leaves_compile_time_out():
    """On the ``jax`` backend the flush that builds a pane shape's programs
    feeds the controller what the warm flush after it does: the seconds of
    the programs' first use are left out."""
    jax.jit(lambda x: x + 1)(np.float32(1.0)).block_until_ready()
    wl = _ab_workload()
    rt = OverloadRuntime(wl, OverloadConfig(shed_policy="none",
                                            micro_batch=1), backend="jax")
    fed = []
    update = rt.controller.update
    rt.controller.update = lambda ms: (fed.append(ms), update(ms))[1]
    n = 9        # a burst length no earlier test compiled for
    for k in range(3):
        t0 = k * rt.pane
        times = t0 + np.linspace(0, rt.pane - 1, n).astype(np.int64)
        rt.offer(EventBatch(AB, np.array([0] + [1] * (n - 1), np.int32),
                            np.sort(times), np.zeros((n, 1)),
                            np.zeros(n, np.int64)))
        rt.step_pane()
    wall = [p.proc_ms for p in rt.metrics.panes]
    compile_ms = wall[0] - fed[0]
    assert compile_ms > 20.0, "the first flush built nothing"
    assert abs(fed[0] - fed[1]) <= 10.0 + 0.2 * compile_ms, (fed, wall)
    assert wall[1] - fed[1] < 1.0    # a warm flush keeps its full time
