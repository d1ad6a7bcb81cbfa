"""Multi-tenant bursty stream over integer ticks (paper Sec. 6.1).

A copy of ``tenant_stream`` and ``overload_stream`` from
``repro.streams.generator`` in the form the ridesharing deployment runs
them (no rate skew, ramp or flash crowd), returning plain arrays, so that
a change to the program cannot move the benchmark's streams.  Tenant ``t``
owns groups ``[t * groups_per_tenant, (t + 1) * groups_per_tenant)`` and
draws its own Poisson per-tick counts and Markov-bursty types from its own
seed; the tenants' streams merge by time, ties in tenant order.  Same
seed, same arrays, bit for bit (``tests/test_rideshare_copies.py``).
"""

from __future__ import annotations

import numpy as np

from bursty_stream import markov_types


def overload_stream(*, n_types: int, n_attrs: int, seed: int,
                    base_events_per_minute: int, minutes: int,
                    n_groups: int, burstiness: float, type_weights,
                    ticks_per_minute: int) -> dict:
    """One tenant: Poisson counts per tick at the base rate."""
    rng = np.random.default_rng(seed)
    total_ticks = minutes * ticks_per_minute
    counts = rng.poisson(base_events_per_minute / ticks_per_minute,
                         total_ticks)
    n = int(counts.sum())
    times = np.repeat(np.arange(total_ticks, dtype=np.int64), counts)
    types = markov_types(rng, n, n_types, type_weights, burstiness)
    attrs = rng.uniform(0.0, 10.0, size=(n, max(1, n_attrs)))
    groups = rng.integers(0, n_groups, size=n)
    return {"type_id": types, "time": times, "attrs": attrs,
            "group": np.asarray(groups, dtype=np.int64)}


def generate(*, n_types: int, n_attrs: int, seed: int, n_tenants: int,
             groups_per_tenant: int, base_events_per_minute: int,
             minutes: int, burstiness: float, type_weights,
             ticks_per_minute: int) -> dict:
    """``{type_id, time, attrs, group}`` arrays, time-sorted."""
    parts = []
    for t in range(n_tenants):
        sub = overload_stream(
            n_types=n_types, n_attrs=n_attrs, seed=seed + 1009 * t,
            base_events_per_minute=base_events_per_minute, minutes=minutes,
            n_groups=groups_per_tenant, burstiness=burstiness,
            type_weights=type_weights, ticks_per_minute=ticks_per_minute)
        sub["group"] = sub["group"] + t * groups_per_tenant
        parts.append(sub)
    time = np.concatenate([p["time"] for p in parts])
    order = np.argsort(time, kind="stable")
    return {"type_id": np.concatenate([p["type_id"] for p in parts])[order],
            "time": time[order],
            "attrs": np.concatenate([p["attrs"] for p in parts])[order],
            "group": np.concatenate([p["group"] for p in parts])[order]}
