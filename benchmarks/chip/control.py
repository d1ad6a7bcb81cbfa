#!/usr/bin/env python3
"""The control of the comparison: the reference one precision step down.

    python3 benchmarks/chip/control.py --config smarthome_q25 \\
        --seeds 11 12 13 --events 40000

The program computes on the device in float32 with matmuls at
``Precision.HIGHEST``.  The control is the reference put in the program's
place and computed one step lower, as float32 matmuls at
``Precision.HIGH`` do (three bf16 passes, ``reference.py``), over the
first ``--events`` events of the configuration's stream: about what a
run sends.
Its window results go through the same comparison as a run's, one
session per tenant, and ``max_rel_err`` has to come out above the
configuration's ``rel_err_limit``.  The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import time

import cell
import check
import reference


def control_reading(config: str, seed: int, n_events: int) -> dict:
    cfg = cell.load_json("configs", config)
    dep = cell.deployment(cfg, seed)
    s = {k: v[:n_events] for k, v in dep.stream.items()}
    t_end = (int(s["time"][-1]) + 1 + dep.pane - 1) // dep.pane * dep.pane
    t0 = time.perf_counter()
    ref = reference.evaluate(s, dep.specs, dep.types, dep.attrs, t_end)
    ctl = reference.evaluate(s, dep.specs, dep.types, dep.attrs, t_end,
                             precision="bf16x3")
    sessions = []
    for t in range(dep.tenants):
        lo, hi = t * dep.groups_per_tenant, (t + 1) * dep.groups_per_tenant
        sessions.append({"tenant": t, "deliveries": [
            ("emit", *k, v, 0.0, 0.0) for k, v in ctl.items()
            if lo <= k[1] < hi]})
    limit = cfg["rel_err_limit"]
    out = check.compare(sessions, ref, dep.groups_per_tenant, limit)
    return {"config": config, "seed": seed, "events": len(s["time"]),
            "windows": len(ref), "max_rel_err": out["numbers"]["max_rel_err"],
            "limit": limit,
            "fails": out["numbers"]["max_rel_err"] > limit,
            "seconds": time.perf_counter() - t0}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--events", type=int, required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        print(json.dumps(control_reading(args.config, seed, args.events)),
              flush=True)


if __name__ == "__main__":
    main()
