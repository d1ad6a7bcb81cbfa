"""The ridesharing deployment served at its own 1 s tick, with the device
boundary in float32 as on a chip.

``conftest.py`` turns jax's x64 mode on, under which the executors keep
64-bit operands on the CPU; a chip takes them in float32.  Here the
boundary is forced to float32 (``platform.device_dtype`` as the executors
see it), so the interpreted Pallas kernels compute what the chip computes:
per-burst coefficients in float32, window values in the host's float64.

* 4 tenants x 2 districts, the paper's ridesharing queries (NOT, SUM, AVG,
  a predicate of its own on Travel in each copy) over 30 s windows sliding
  by 5 s: the values leave float32's range, the bursts are longer than 24
  rows, and both the masked and the dense kernels run;
* the served results agree with the numpy engine in float64, and at a tiny
  size with the brute-force trend enumeration;
* the sum round's and count round's spans sum to their counters, and the
  job and negation counters count what the plan holds; so do the fold
  executor's snapshot-fill spans and counters.
"""

import math

import numpy as np
import pytest

from repro.core import batch_exec, fold_exec
from repro.core.baselines.brute import brute_run
from repro.core.engine import (HamletRuntime, PaneMicroBatcher,
                               PaneProcessor, _GroupPlan, _NegStep)
from repro.core.events import EventBatch
from repro.launch.hamlet_service import ridesharing_workload
from repro.obs import Observability
from repro.overload import OverloadConfig
from repro.serve import ServingFrontend
from repro.streams.generator import (RIDESHARING_SCHEMA, TenantStreamConfig,
                                     tenant_stream)

F32_MAX = float(np.finfo(np.float32).max)
# f32 coefficients carry a relative rounding of 2^-24 per operation, and
# a burst's chain is at most ~70 rows long here.  The served values read
# 2.4e-8 to 7.8e-8 against float64 in these tests; with the device
# matmuls one step down (Precision.HIGH, three bf16 passes) they read
# 1.16e-6 to 2.44e-6, and with one bf16 pass 6e-4 to 9e-4.  The limit
# lies between, as the ridesharing cell's rel_err_limit does.
RTOL = 5e-7


def _f32(dtype):
    dt = np.dtype(dtype)
    return np.dtype(dt.kind + "4") if dt.itemsize == 8 else dt


@pytest.fixture
def f32_boundary(monkeypatch):
    """The chip's device boundary: 64-bit operands cross as 32-bit."""
    monkeypatch.setattr(batch_exec, "device_dtype", _f32)
    monkeypatch.setattr(fold_exec, "device_dtype", _f32)


def _stream(epm, minutes, seed):
    s = tenant_stream(TenantStreamConfig(
        schema=RIDESHARING_SCHEMA, n_tenants=4, groups_per_tenant=2,
        base_events_per_minute=epm, minutes=minutes, burstiness=0.85,
        type_weights=(1, 1, 6, 1, 1, 1), seed=seed))
    return EventBatch(s.schema, s.type_id, s.time, s.attrs, s.group,
                      seq=np.arange(len(s), dtype=np.int64))


def _serve(wl, stream, obs=None):
    """Two sessions per tenant push their stride halves pane by pane into
    one front-end on the ``pallas`` backend, K=4; the drained results."""
    fe = ServingFrontend(wl, backend="overload",
                         overload=OverloadConfig(shed_policy="none",
                                                 micro_batch=4),
                         np_backend="pallas", groups_per_tenant=2, obs=obs)
    handles, parts = [], []
    for i in range(8):
        t = i % 4
        idx = np.flatnonzero(stream.group // 2 == t)[i // 4::2]
        handles.append(fe.open_session(tenant=t))
        parts.append(stream.select(idx))
    t_end = ((int(stream.time.max()) // fe.pane) + 1) * fe.pane
    for c0 in range(0, t_end, fe.pane):
        for h, p in zip(handles, parts):
            h.submit(p.time_slice(c0, c0 + fe.pane))
            h.advance_to(c0 + fe.pane)
        fe.pump()
    for h in handles:
        h.close()
    return fe.drain(), t_end


def _max_rel(got, want):
    assert want and got.keys() == want.keys()
    worst = 0.0
    for k, wv in want.items():
        assert got[k].keys() == wv.keys(), k
        for a, w in wv.items():
            g = got[k][a]
            if math.isnan(w) or math.isnan(g):
                assert math.isnan(w) and math.isnan(g), (k, a)
                continue
            worst = max(worst, abs(g - w) / abs(w) if w else abs(g))
    return worst


@pytest.fixture(scope="module")
def served():
    """The served run in float32, traced, with every pane's plan and every
    built plan recorded: 4 queries over two stream-minutes at 1,000 events
    a minute per tenant, the least rate at which the windows leave
    float32's range."""
    wl = ridesharing_workload(4)
    stream = _stream(1000, 2, seed=2**31 + 15)
    drained, built = [], []
    drain, build = PaneMicroBatcher.drain, PaneProcessor._build_steps

    def drain_rec(self):
        pend = drain(self)
        drained.extend(p.steps for p in pend)
        return pend

    def build_rec(self, plan_bursts, stats):
        out = build(self, plan_bursts, stats)
        built.append(out[0])
        return out

    mp = pytest.MonkeyPatch()
    mp.setattr(batch_exec, "device_dtype", _f32)
    mp.setattr(fold_exec, "device_dtype", _f32)
    mp.setattr(PaneMicroBatcher, "drain", drain_rec)
    mp.setattr(PaneProcessor, "_build_steps", build_rec)
    obs = Observability(audit=False)
    try:
        got, t_end = _serve(wl, stream, obs)
    finally:
        mp.undo()
    return dict(wl=wl, stream=stream, got=got, t_end=t_end, obs=obs,
                drained=drained, built=built)


def test_served_f32_matches_float64_engine(served):
    want = HamletRuntime(served["wl"], backend="np").run(served["stream"],
                                                         served["t_end"])
    assert _max_rel(served["got"], want) <= RTOL
    vals = [v for r in want.values() for v in r.values() if v == v]
    # window values beyond float32 reach the host intact
    assert max(vals) > F32_MAX


def test_served_f32_runs_both_kernels_on_long_bursts(served):
    c = served["obs"].registry.collect()
    assert c["batch_exec.launches.masked"] > 0
    assert c["batch_exec.launches.dense"] > 0
    steps = [s for st in served["drained"] for s in st
             if isinstance(s, _GroupPlan)]
    assert max(s.b for s in steps if not s.trivial) > 24


def test_round_counters_equal_their_spans(served):
    obs = served["obs"]
    c = obs.registry.collect()
    evs = obs.tracer._snapshot()
    n = {}
    for span, counter in (("execute.count", "batch_exec.count_round_s"),
                          ("execute.sum", "batch_exec.sum_round_s")):
        xs = [e for e in evs if e[0] == "X" and e[1] == span]
        n[span] = len(xs)
        assert xs, span
        assert sum(e[4] for e in xs) / 1e6 == pytest.approx(
            c[counter], rel=1e-9, abs=1e-12), span
    assert n["execute.count"] == n["execute.sum"] == len(
        [e for e in evs if e[0] == "X" and e[1] == "flush"])


def test_fill_counters_equal_their_spans(served):
    """One ``finalize.fill`` span per divergent fold bucket, summing to
    ``fold_exec.fill_s``; the SUM and AVG units' buckets take the stacked
    fill."""
    c = served["obs"].registry.collect()
    xs = [e for e in served["obs"].tracer._snapshot()
          if e[0] == "X" and e[1] == "finalize.fill"]
    assert c["fold_exec.fill.stacked"] > 0
    assert len(xs) == c["fold_exec.fill.stacked"] + c.get(
        "fold_exec.fill.sequential", 0)
    assert sum(e[4] for e in xs) / 1e6 == pytest.approx(
        c["fold_exec.fill_s"], rel=1e-9, abs=1e-12)


def test_job_and_negation_counters_match_the_plan(served):
    c = served["obs"].registry.collect()
    groups = [s for st in served["drained"] for s in st
              if isinstance(s, _GroupPlan)]
    assert c["batch_exec.jobs.count"] == len(groups)
    assert c["batch_exec.jobs.sum"] == sum(len(s.sum_units) for s in groups)
    assert c["batch_exec.jobs.sum"] > c["batch_exec.jobs.count"] > 0
    negs = sum(isinstance(s, _NegStep) for st in served["built"] for s in st)
    assert c["engine.plan.neg_steps"] == negs > 0
    assert c["engine.plan.graphlets"] == sum(
        len(st) for st in served["built"]) - negs


def test_tracing_off_registers_no_round_counters(f32_boundary):
    obs = Observability.disabled()
    got, _ = _serve(ridesharing_workload(3), _stream(200, 1, seed=4), obs)
    assert got
    c = obs.registry.collect()
    assert "batch_exec.count_round_s" not in c
    assert "batch_exec.sum_round_s" not in c
    assert c["batch_exec.jobs.count"] > 0 and c["batch_exec.jobs.sum"] > 0
    assert len(obs.tracer) == 0


@pytest.mark.parametrize("seed", [7, 2**31 + 3])
def test_served_f32_matches_brute_force(f32_boundary, seed):
    wl = ridesharing_workload(4)
    stream = _stream(40, 1, seed)
    got, t_end = _serve(wl, stream)
    want = brute_run(wl, stream, t_end)
    # the served path reports a group's windows from its first pane on;
    # the enumeration's earlier windows are empty
    early = {k: v for k, v in want.items() if k not in got}
    assert all(v == 0 or v != v for r in early.values() for v in r.values())
    want = {k: v for k, v in want.items() if k in got}
    assert _max_rel(got, want) <= RTOL
    # AVG with no trend is NaN on both sides
    assert any(math.isnan(v) for r in got.values() for v in r.values())
