#!/usr/bin/env python3
"""On-chip benchmark of the served Hamlet path: one cell, one run.

    python3 benchmarks/chip/run.py --workload smarthome_q25.closed \\
        --seed 7 --seconds 30 --trace 0

The cell ``<config>.<traffic>`` is looked up in ``BENCHMARK.json`` at the
checkout's root; its deployment and mix are files under this directory
(see ``cell.py``).  This process holds the chip and serves the stream
through ``ServingServer`` over ``ServingFrontend`` (the ``overload``
backend on the device kernels); the clients run in a process of their own
(``loadgen.py``) that never opens the chip.

Timeline: set-up (compile cache, stream, server, clients), a warm-up on
the first ``warmup_panes`` panes of the cell's own stream, the measured
window of ``--seconds``, then the drain, the reference and the
comparison, which are not counted in ``setup_s``.  The traffic is a
closed loop: every session submits its next pane as soon as its credits
allow.  ``events_per_s`` counts the events of the panes the engine
finished (executed, finalized and folded) in the window.  ``--trace 1``
also records a profiler trace of a few seconds in the middle of the
window and reports the per-layer metrics instead of the end-to-end
ones.

Exits non-zero with no result line when JAX finds no TPU or fewer chips
than the cell asks for, or when the program is not in the checkout.  The
last line of stdout is the result; the last lines of stderr are the
numbers compared, each with its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

import cell  # noqa: E402
import check  # noqa: E402
import devtrace  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402

HERE = Path(__file__).resolve().parent
TRACE_S = 3.0            # longest traced span, mid-window
DRAIN_S = 300.0
SAMPLE_S = 0.01          # the work sampler's polling period
STACKS_AT_S = 300.0       # thread stacks to stderr if still running
# fired for every program the process first uses: a compile, or a load
# from the persistent cache (which also fires ``_CACHE_HIT_EVENT``)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoDevice(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class WorkSampler(threading.Thread):
    """Samples the work the engine has finished: at every change,
    ``(perf_counter, events, tick)``, where ``events`` counts the admitted
    events of every pane whose micro-batch has been executed, finalized
    and folded, and ``tick`` is the end of the last such pane.  It polls
    every ``SAMPLE_S``: a flush finishes every second or so, and a thread
    that wakes more often contends for the interpreter with the engine."""

    def __init__(self, fe):
        super().__init__(name="work-sampler", daemon=True)
        self.fe = fe
        self.points: list[tuple[float, int, int]] = [
            (time.perf_counter(), 0, 0)]
        self._halt = threading.Event()

    def run(self) -> None:
        panes = program.finished_panes(self.fe)
        seen = events = tick = 0
        while not self._halt.is_set():
            n = len(panes)
            if n > seen:
                now = time.perf_counter()
                for p in panes[seen:n]:
                    events += p.admitted
                    tick = max(tick, p.t0)
                seen = n
                self.points.append((now, events, tick + self.fe.pane))
            self._halt.wait(SAMPLE_S)

    def events_at(self, t: float) -> float:
        """Finished events at ``t``, linear between two samples."""
        return float(np.interp(t, [p[0] for p in self.points],
                               [p[1] for p in self.points]))

    def tick_at(self, t: float) -> int:
        """End tick of the last pane finished by ``t``."""
        b = 0
        for ts, _, v in self.points:
            if ts > t:
                break
            b = v
        return b

    def stop(self) -> None:
        self._halt.set()
        self.join()


class Generator:
    """The load generator process and its JSON-lines channel."""

    def __init__(self, params: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py")], cwd=str(HERE),
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True)
        self.msgs: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, daemon=True).start()
        self.send(params)

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.msgs.put(json.loads(line))
        self.msgs.put({"msg": "exit"})

    def send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def expect(self, what: str, timeout: float) -> dict:
        msg = self.msgs.get(timeout=timeout)
        if msg.get("msg") != what:
            raise RuntimeError(f"load generator sent {msg}, expected {what}")
        if msg.get("errors"):
            raise RuntimeError(f"load generator: {msg['errors']}")
        return msg

    def close(self) -> None:
        """Wait for the process to end; kill it if it does not."""
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and (platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"need {chips} TPU chip(s); JAX reports "
                       f"{len(devs)} {platform!r} device(s)")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, overrides: dict | None = None) -> dict:
    """One run of a cell."""
    c = cell.load_cell(workload, overrides=overrides)
    if c.traffic["loop"] != "closed":
        raise ValueError(f"traffic {c.traffic_name!r}: only a closed loop "
                         "is driven")
    device = device_info(c.chips, require_tpu)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cell.ROOT / ".jax_cache")
    program.import_program()
    import jax
    from repro.kernels.platform import enable_compile_cache
    enable_compile_cache()

    compiles: list[float] = []
    cache_hits: list[float] = []

    def on_compile(event, secs, **_kw):
        if event == _COMPILE_EVENT:
            compiles.append(time.perf_counter())

    def on_event(event, **_kw):
        if event == _CACHE_HIT_EVENT:
            cache_hits.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_event)

    dep = cell.deployment(c.config, seed)
    warm = c.config["warmup_panes"] * dep.pane
    wl = program.workload(dep.specs, dep.types, dep.attrs)
    obs = program.observability(trace)
    srv, fe, host, port = program.serve(wl, c.config["serve"],
                                        dep.groups_per_tenant, obs)
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    out_path = os.path.join(tmp, "loadgen.pkl")
    gen = Generator({"workload": workload, "seed": seed, "host": host,
                     "port": port, "out": out_path, "overrides": overrides})
    sampler = WorkSampler(fe)
    try:
        gen.expect("ready", 600)
        _phase("clients ready")
        sampler.start()
        gen.send({"cmd": "start"})
        _wait(lambda: sampler.points[-1][2] >= warm, 1200, "the warm-up")
        t_ws = time.perf_counter()
        t_we = t_ws + seconds
        gen.send({"cmd": "stop_at", "t": t_we})
        setup_s = t_ws - T_PROCESS
        _phase("warm-up done, window opens")
        st0, ct0 = program.engine_stats(fe), program.counters(obs)
        tr = None
        if trace:
            span = min(TRACE_S, seconds / 2)
            tr = _trace(t_ws + (seconds - span) / 2, span, obs, tmp)
        _sleep_until(t_we)
        st1, ct1 = program.engine_stats(fe), program.counters(obs)
        _phase("window closed")
        gen.expect("closed", seconds + 600)
        _phase("sessions stopped")
        _wait(lambda: all(s["closed"] for s in
                          fe.summary()["sessions"].values()), 60,
              "sessions to close")
        srv.drain(timeout=DRAIN_S)
        _phase("drained")
        t_end = fe.sealed_to()
        sampler.stop()
        gen.expect("done", 600)
        _phase("deliveries collected")
        lost = program.lost_events(fe)
        sealed = fe.summary()["sealed_events"]
        peak = memory_peak()
    finally:
        gen.close()
        if sampler.is_alive():
            sampler.stop()
        srv.stop()
    faulthandler.cancel_dump_traceback_later()
    with open(out_path, "rb") as f:
        got = pickle.load(f)
    shutil.rmtree(tmp)
    del srv, fe, obs, wl
    gc.collect()

    # -------------------------------------------------- window numbers
    times = dep.stream["time"]
    ev_window = sampler.events_at(t_we) - sampler.events_at(t_ws)
    b_ws, b_we = sampler.tick_at(t_ws), sampler.tick_at(t_we)
    n_compiles = sum(t_ws <= t <= t_we for t in compiles)
    n_loaded = sum(t_ws <= t <= t_we for t in cache_hits)
    by_pane: dict = {}
    for t in compiles:
        k = sampler.tick_at(t) // dep.pane // 10 * 10
        by_pane[k] = by_pane.get(k, 0) + 1
    sent_idx = np.concatenate([dep.session_index(i)[:s.get("sent", 0)]
                               for i, s in enumerate(got["sessions"])])
    sent_idx.sort()
    within = dep.within()

    def in_scope(key):
        return b_ws < key[2] + within[key[0]] <= b_we

    log(f"cell {workload}: seed {seed}, {dep.sessions} sessions, "
        f"{len(sent_idx)} of {len(times)} stream events sent, "
        f"{sealed} sealed; warm-up {warm} ticks; window "
        f"{t_we - t_ws:.3f} s, {ev_window:.1f} events finished, "
        f"finished panes to tick {b_ws} -> {b_we}")
    log(f"compiles inside the window: {n_compiles}, {n_loaded} of them "
        f"loaded from the persistent cache (of {len(compiles)} programs in "
        f"the run, {len(cache_hits)} loaded); programs first used by "
        f"finished pane, in tens: {dict(sorted(by_pane.items()))}")
    exhausted = [i for i, s in enumerate(got["sessions"])
                 if s.get("exhausted")]
    if exhausted:
        raise RuntimeError(f"sessions {exhausted} ran out of stream: "
                           "the configuration's stream is too short")

    # -------------------------------------------------- reference
    t0 = time.perf_counter()
    sent = {k: dep.stream[k][sent_idx] for k in
            ("type_id", "time", "attrs", "group")}
    ref = reference.evaluate(sent, dep.specs, dep.types, dep.attrs, t_end)
    ref_s = time.perf_counter() - t0
    _phase("reference done")
    limit = c.config["rel_err_limit"]
    cmp = check.compare(got["sessions"], ref, dep.groups_per_tenant, limit,
                        scope=in_scope)
    numbers = dict(cmp["numbers"], lost_events=sum(lost.values()),
                   unsealed_events=len(sent_idx) - sealed)
    top = max((abs(v) for vals in ref.values() for v in vals.values()
               if v == v), default=0.0)
    log(f"reference: plain f64 trend recurrence (benchmarks/chip/"
        f"reference.py) over {len(sent_idx)} events to t_end {t_end}: "
        f"{len(ref)} windows in {ref_s:.3f} s; largest value {top:.4e} "
        f"(float32 max 3.4028e+38); worst error at {cmp['worst_at']}; "
        f"lost events {lost}")

    # -------------------------------------------------- metrics
    ctx = _context(got, t_ws, t_we, ev_window, st0, st1, ct0, ct1, tr,
                   sampler)
    _log_generator(ctx)
    if trace:
        metrics = _per_layer(c, ctx)
    else:
        metrics = _end_to_end(c, ctx, setup_s)
    correct, checks = check.verdict(numbers, check.limits(limit))
    _phase("compared")
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": int(cmp["attempted"]),
              "failed": int(cmp["failed"]), "metrics": metrics,
              "device": device}
    if tr is not None:
        result["device"].update(busy_s=tr["busy_s"],
                                window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["labelled_gaps"]}
    result["checks"] = checks
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return result


def _phase(what: str) -> None:
    log(f"[{time.perf_counter() - T_PROCESS:9.3f} s] {what}")


def _wait(cond, timeout: float, what: str) -> None:
    t_end = time.perf_counter() + timeout
    while not cond():
        if time.perf_counter() > t_end:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.005)


def _sleep_until(t: float) -> None:
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


def _trace(t_start: float, span: float, obs, tmp: str) -> dict:
    """Profile ``span`` seconds from ``t_start``; reduce the trace and
    label its idle gaps with the engine's phase spans."""
    import jax

    log_dir = os.path.join(tmp, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # no per-call Python events
    opts.host_tracer_level = 1         # the window annotation
    opts.enable_hlo_proto = False
    _sleep_until(t_start)
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        a0 = time.perf_counter()
        _sleep_until(a0 + span)
        a1 = time.perf_counter()
    spans = _spans(obs)
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(log_dir)
    evs = devtrace.events(path)
    out = devtrace.reduce(evs)
    offset = out["window"][0] - a0
    out["labelled_gaps"] = devtrace.label_gaps(out["gaps"], spans, offset)
    out["perf_window"] = (a0, a1)
    log(f"trace: {out['modules']} device programs, busy {out['busy_s']:.6f}"
        f" s of {out['window_s']:.6f} s; kernels {out['kernel_s']}; "
        f"{os.path.getsize(path)} bytes")
    return out


def _spans(obs) -> list[tuple[str, float, float]]:
    """The tracer's phase spans as ``(name, start, end)`` on
    ``perf_counter``."""
    tracer = obs.tracer
    for _ in range(100):
        try:
            evs = list(tracer._events)
            break
        except RuntimeError:      # the ring moved under the copy; again
            continue
    else:
        return []
    t0 = tracer._t0
    return [(name, t0 + ts * 1e-6, t0 + (ts + dur) * 1e-6)
            for ph, name, _cat, ts, dur, _tid, _a in evs if ph == "X"]


def _context(got, t_ws, t_we, ev_window, st0, st1, ct0, ct1, tr, sampler):
    waits = [wait * 1e3 for s in got["sessions"]
             for _tick, t_call, wait in s.get("submits", ())
             if t_ws <= t_call <= t_we]
    ctx = SimpleNamespace(
        window_s=t_we - t_ws, events=ev_window,
        stats=({k: st1[k] - st0[k] for k in st0}),
        counters={k: ct1.get(k, 0) - ct0.get(k, 0) for k in ct1},
        submit_wait_ms=waits, trace=tr, traced_events=None)
    if tr is not None:
        a0, a1 = tr["perf_window"]
        ctx.traced_events = sampler.events_at(a1) - sampler.events_at(a0)
    return ctx


def _log_generator(ctx) -> None:
    if ctx.submit_wait_ms:
        log(f"generator: credit wait per submit p50 "
            f"{np.percentile(ctx.submit_wait_ms, 50):.3f} ms, p95 "
            f"{np.percentile(ctx.submit_wait_ms, 95):.3f} ms over "
            f"{len(ctx.submit_wait_ms)} submits")
    log(f"engine over the window: {json.dumps(ctx.stats)}")


def _end_to_end(c, ctx, setup_s: float) -> dict:
    have = {"setup_s": setup_s, "events_per_s": ctx.events / ctx.window_s}
    out = {}
    for m in c.end_to_end:
        if m["name"] not in have:
            raise RuntimeError(f"{m['name']} was not measured in {c.name}")
        out[m["name"]] = {"value": have[m["name"]], "unit": m["unit"]}
    return out


def _per_layer(c, ctx) -> dict:
    out = {}
    for m in c.per_layer:
        v = cell.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a run that has not ended by then shows where each thread stands
    faulthandler.dump_traceback_later(STACKS_AT_S - (time.perf_counter()
                                                     - T_PROCESS))
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoDevice as e:
        log(f"run.py: {e}")
        return 3
    except ImportError as e:
        log(f"run.py: the program is not in this checkout: {e}")
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        # the served path's threads may still hold the process: report and
        # leave without waiting for them (the load generator is stopped)
        import traceback
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    sys.exit(code)
