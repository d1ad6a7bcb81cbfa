#!/usr/bin/env python3
"""Chip smoke test: the served Hamlet path on a TPU, checked against numpy.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # 4-shard service on four chips

Deployment: the paper's ridesharing workload at the top of its query sweep
(``ridesharing_workload(25)``, the 25 of ``benchmarks/fig11_scale.py``) over
a ridesharing tenant stream at the paper's 10,000 events/min: 4 tenants x 2
districts, 2 minutes of stream time, made from ``--seed``.  One tick is 0.1 s
(600 ticks per minute), so the queries' 20-30 tick windows span 2-3 s of
stream.  At the generator's default 1 s ticks the trend counts of a 30 s
window reach ~1e128, beyond f32 range; on-device counts are f32 until the
engine has a count form that holds for long bursts.

Path: 8 ``ServingClient`` sessions (2 per tenant) stream their split of the
events over the loopback socket into one ``ServingServer`` /
``ServingFrontend`` in this process.  The engine runs the ``pallas``
backend with ``shed_policy="none"``, so no event is shed.  A cold pass
compiles every program the stream needs; a warm pass on a fresh front-end
must compile nothing.  The served engine folds its cross-group flushes on
the host (no such flush forms a scannable fold plan), so a last pass runs
the batch entry point, ``HamletRuntime(backend="pallas")``, over the same
stream: its per-group flushes reach the device fold scan.  ``--chips 4``
instead serves the stream through the sharded backend twice, with 1 shard
(chip 0) and with 4 shards (shard i on chip i, tenant i pinned to shard
i), and compares the two.

Reference: ``HamletRuntime(backend="np")`` (float64, host) on the merged
stream.  Window keys must be identical; values must agree within ``RTOL``.

Exits non-zero, with no result line, when jax finds no TPU, when the
``repro`` package is not next to this script, or when any check fails.
The last line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Relative tolerance of the f32 device path against the f64 reference.
# Every value is built from sums of products of non-negative terms (trend
# counts, attribute sums over non-negative attributes, their ratio for
# AVG): no cancellation, so the relative error is at most the number of
# rounded operations along one accumulation path times the f32 unit
# roundoff 2^-24 ~ 6e-8.  A path here is a 128-row tile solve (7 doubling
# rounds of 128-term dot products), the cross-tile matmuls and a fold
# chain over a 6-pane window: well under 1,000 roundings, so 6e-5.
RTOL = 1e-4
F32_MAX = 3.4028234663852886e38

TENANTS, GROUPS_PER_TENANT, SESSIONS = 4, 2, 8
EVENTS_PER_MINUTE, QUERIES, TICKS_PER_MINUTE, MINUTES = 10_000, 25, 600, 2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- phases


def deployment(seed: int, minutes: int = MINUTES):
    """(workload, merged stream with producer seq, t_end)."""
    import numpy as np

    from repro.core.events import EventBatch
    from repro.launch.hamlet_service import ridesharing_workload
    from repro.streams.generator import (RIDESHARING_SCHEMA,
                                         TenantStreamConfig, tenant_stream)

    wl = ridesharing_workload(QUERIES)
    s = tenant_stream(TenantStreamConfig(
        schema=RIDESHARING_SCHEMA, n_tenants=TENANTS,
        groups_per_tenant=GROUPS_PER_TENANT,
        base_events_per_minute=EVENTS_PER_MINUTE // TENANTS,
        minutes=minutes, type_weights=(1, 1, 6, 1, 1, 1), seed=seed,
        ticks_per_minute=TICKS_PER_MINUTE))
    # producer seq = merged position: the served merge then breaks
    # timestamp ties exactly like the batch reference
    s = EventBatch(schema=s.schema, type_id=s.type_id, time=s.time,
                   attrs=s.attrs, group=s.group,
                   seq=np.arange(len(s), dtype=np.int64))
    return wl, s, minutes * TICKS_PER_MINUTE


def reference(wl, stream, t_end) -> dict:
    from repro.core.engine import HamletRuntime

    return HamletRuntime(wl, backend="np").run(stream, t_end=t_end)


def batch_pass(wl, stream, t_end, *, backend: str):
    """The batch entry point over the merged stream: (results, wall
    seconds, metrics registry dict)."""
    from repro.core.engine import HamletRuntime
    from repro.obs import Observability

    obs = Observability.disabled()
    t0 = time.perf_counter()
    res = HamletRuntime(wl, backend=backend, obs=obs).run(stream,
                                                          t_end=t_end)
    return res, time.perf_counter() - t0, obs.registry.collect()


def session_parts(stream):
    """Session i serves tenant i mod TENANTS; a tenant's events are
    stride-split across its sessions."""
    import numpy as np

    per = SESSIONS // TENANTS
    parts = []
    for i in range(SESSIONS):
        t = i % TENANTS
        lo, hi = t * GROUPS_PER_TENANT, (t + 1) * GROUPS_PER_TENANT
        idx = np.flatnonzero((stream.group >= lo) & (stream.group < hi))
        parts.append((t, stream.select(idx[i // TENANTS::per])))
    return parts


def served_pass(wl, stream, t_end, *, backend: str, n_shards: int = 0):
    """Drive the stream through the socket front-end once.

    ``n_shards=0`` serves one overload engine; otherwise the sharded
    service with that many shards.  Returns (results, wall seconds,
    merged metrics registry dict)."""
    from repro.obs import Observability
    from repro.overload import OverloadConfig
    from repro.serve import ServingClient, ServingFrontend, ServingServer
    from repro.shardsvc import ShardServiceConfig

    ov = OverloadConfig(shed_policy="none", micro_batch=4)
    obs = Observability.disabled()
    if n_shards:
        fe = ServingFrontend(
            wl, backend="sharded", np_backend=backend,
            shard_cfg=ShardServiceConfig(
                n_shards=n_shards, groups_per_tenant=GROUPS_PER_TENANT,
                admission="none", overload=ov, obs=True),
            groups_per_tenant=GROUPS_PER_TENANT)
        # tenant i on shard i mod n_shards: at 4 tenants the hash ring
        # leaves one of 4 shards empty, and every chip should work
        svc = fe._backend.svc
        for g in range(TENANTS * GROUPS_PER_TENANT):
            svc.placement.override(g, g // GROUPS_PER_TENANT % n_shards)
    else:
        fe = ServingFrontend(wl, backend="overload", overload=ov,
                             np_backend=backend,
                             groups_per_tenant=GROUPS_PER_TENANT, obs=obs)
    srv = ServingServer(fe)
    host, port = srv.start()
    parts = session_parts(stream)
    opened = threading.Barrier(len(parts))
    errors: list[BaseException] = []

    def client(tenant, part):
        c = ServingClient(host, port, tenant=tenant)
        try:
            opened.wait(timeout=60.0)
            pane = c.pane
            for c0 in range(0, t_end, pane):
                c.submit(part.time_slice(c0, c0 + pane), timeout=600.0)
                c.advance_to(c0 + pane)
            c.close()
            c.wait_end(timeout=1200.0)
        except BaseException as e:   # reported by the owner below
            errors.append(e)
            raise
        finally:
            c.shutdown()

    threads = [threading.Thread(target=client, args=p, name=f"session-{i}")
               for i, p in enumerate(parts)]
    t0 = time.perf_counter()
    try:
        for th in threads:
            th.start()
        deadline = time.perf_counter() + 1200.0
        while not errors:
            sess = fe.summary()["sessions"]
            if len(sess) == len(parts) and all(s["closed"]
                                               for s in sess.values()):
                break
            if time.perf_counter() > deadline:
                fail("sessions never closed")
            time.sleep(0.01)
        if errors:
            fail(f"session failed: {errors[0]!r}")
        res = srv.drain(timeout=600.0)
        for th in threads:
            th.join(timeout=60.0)
            if th.is_alive():
                fail(f"{th.name} did not finish")
        wall = time.perf_counter() - t0
        if errors:
            fail(f"session failed: {errors[0]!r}")
        metrics = (fe.collect()["engine"]["metrics"] if n_shards
                   else obs.registry.collect())
        if n_shards:
            placement = [sorted({g for g in range(TENANTS * GROUPS_PER_TENANT)
                                 if svc.placement.shard_of(g) == w.shard_id})
                         for w in svc.workers]
            devices = [str(w.device) for w in svc.workers]
            metrics = dict(metrics, _placement=placement, _devices=devices)
    finally:
        srv.stop()
    return res, wall, metrics


def compare(got: dict, ref: dict, tag: str) -> float:
    """Largest relative error of ``got`` against ``ref``; fails on any
    key, NaN-pattern or tolerance mismatch."""
    import math

    if got.keys() != ref.keys():
        fail(f"{tag}: window keys differ: {len(got.keys() - ref.keys())} "
             f"extra, {len(ref.keys() - got.keys())} missing")
    worst, where = 0.0, None
    for k, rv in ref.items():
        gv = got[k]
        if gv.keys() != rv.keys():
            fail(f"{tag}: aggregates differ at {k}")
        for a, r in rv.items():
            g = gv[a]
            if math.isnan(r) or math.isnan(g):
                if not (math.isnan(r) and math.isnan(g)):
                    fail(f"{tag}: NaN mismatch at {k} {a}: {g} vs {r}")
                continue
            err = abs(g - r) / abs(r) if r else abs(g)
            if err > worst:
                worst, where = err, (k, a, g, r)
    if worst > RTOL:
        fail(f"{tag}: relative error {worst:.3e} > {RTOL:.0e} at {where}")
    return worst


def check_reference_range(ref: dict) -> int:
    import math

    n = 0
    for k, v in ref.items():
        for a, x in v.items():
            if math.isnan(x):
                continue
            if not abs(x) <= F32_MAX:
                fail(f"reference value {x!r} at {k} {a} is beyond f32 range")
            n += 1
    return n


def launches(metrics: dict) -> dict:
    return {"propagate": int(metrics.get("batch_exec.launches.masked", 0)),
            "dense": int(metrics.get("batch_exec.launches.dense", 0)),
            "fold_scan": int(metrics.get("fold_exec.scan_launches", 0))}


# ------------------------------------------------------------------ main


def run_one_chip(wl, stream, t_end, ref) -> None:
    from repro.kernels.platform import CompileCounter

    with CompileCounter() as cold_cc:
        cold, cold_s, cold_m = served_pass(wl, stream, t_end,
                                           backend="pallas")
    say(f"cold pass: compiles={cold_cc.compiles} "
        f"compile_s={cold_cc.seconds:.3f} "
        f"persistent_cache_hits={cold_cc.cache_hits} wall_s={cold_s:.3f}")
    with CompileCounter() as warm_cc:
        warm, warm_s, warm_m = served_pass(wl, stream, t_end,
                                           backend="pallas")
    say(f"warm pass: compiles={warm_cc.compiles} wall_s={warm_s:.3f} "
        f"events_per_s={len(stream) / warm_s:.1f}")
    lc = launches(warm_m)
    say(f"warm pass launches: {json.dumps(lc)}")
    err_cold = compare(cold, ref, "cold pass")
    err_warm = compare(warm, ref, "warm pass")
    say(f"max relative error vs numpy: cold={err_cold:.3e} "
        f"warm={err_warm:.3e} (rtol {RTOL:.0e})")
    if warm_cc.compiles:
        fail(f"warm pass compiled {warm_cc.compiles} programs")
    if lc["propagate"] <= 0 or lc["dense"] <= 0:
        fail(f"a served device path never launched: {lc}")
    with CompileCounter() as batch_cc:
        batch, batch_s, batch_m = batch_pass(wl, stream, t_end,
                                             backend="pallas")
    lb = launches(batch_m)
    err_batch = compare(batch, ref, "batch pass")
    say(f"batch pass: compiles={batch_cc.compiles} wall_s={batch_s:.3f} "
        f"launches={json.dumps(lb)} max relative error={err_batch:.3e}")
    if min(lb.values()) <= 0:
        fail(f"a batch device path never launched: {lb}")


def run_four_chips(wl, stream, t_end, ref) -> None:
    import jax

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs four devices, found {len(jax.devices())}")
    one, one_s, one_m = served_pass(wl, stream, t_end, backend="pallas",
                                    n_shards=1)
    say(f"1 shard: wall_s={one_s:.3f} devices={one_m['_devices']} "
        f"launches={json.dumps(launches(one_m))}")
    four, four_s, four_m = served_pass(wl, stream, t_end, backend="pallas",
                                       n_shards=4)
    say(f"4 shards: wall_s={four_s:.3f} devices={four_m['_devices']} "
        f"groups={four_m['_placement']} "
        f"launches={json.dumps(launches(four_m))}")
    if len(set(four_m["_devices"])) != 4:
        fail(f"4 shards did not land on 4 devices: {four_m['_devices']}")
    e_one = compare(one, ref, "1 shard")
    e_four = compare(four, ref, "4 shards")
    e_pair = compare(four, one, "4 shards vs 1 shard")
    say(f"max relative error: 1 shard vs numpy={e_one:.3e} "
        f"4 shards vs numpy={e_four:.3e} 4 vs 1 shard={e_pair:.3e} "
        f"(rtol {RTOL:.0e})")
    for tag, m in (("1 shard", one_m), ("4 shards", four_m)):
        lc = launches(m)
        if lc["propagate"] <= 0 or lc["dense"] <= 0:
            fail(f"{tag}: a served device path never launched: {lc}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"no TPU found: jax reports platform {platform!r}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.kernels.platform import (enable_compile_cache,
                                            interpret_kernels)
    except ImportError as e:
        fail(f"the repro package is not next to this script: {e}")
    if interpret_kernels():
        fail("Pallas kernels would run in the interpreter")
    say(f"device: platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)} x64={jax.config.jax_enable_x64}")
    say(f"compile cache: {enable_compile_cache()}")

    wl, stream, t_end = deployment(args.seed)
    say(f"deployment: ridesharing, {len(wl.queries)} queries, "
        f"{TENANTS} tenants x {GROUPS_PER_TENANT} districts, "
        f"{EVENTS_PER_MINUTE} events/min, {MINUTES} min, "
        f"{len(stream)} events, {SESSIONS} sessions, seed {args.seed}")
    t0 = time.perf_counter()
    ref = reference(wl, stream, t_end)
    n_vals = check_reference_range(ref)
    say(f"numpy reference: windows={len(ref)} finite_values={n_vals} "
        f"wall_s={time.perf_counter() - t0:.3f}")

    if args.chips == 4:
        run_four_chips(wl, stream, t_end, ref)
    else:
        run_one_chip(wl, stream, t_end, ref)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
