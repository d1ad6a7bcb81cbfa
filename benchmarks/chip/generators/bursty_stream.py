"""Bursty stream over integer ticks (paper Sec. 6.1).

A copy of ``bursty_stream`` and ``_markov_types`` from
``repro.streams.generator``, returning plain arrays, so that a change to
the program cannot move the benchmark's streams.  Same seed, same
arrays, bit for bit (``tests/test_copies.py``).
"""

from __future__ import annotations

import numpy as np


def markov_types(rng, n: int, n_types: int, weights, burstiness: float
                 ) -> np.ndarray:
    """Markov-switching type sequence: with prob ``burstiness`` the next
    event repeats the current type (a burst); otherwise it redraws from the
    type distribution."""
    w = np.asarray(np.ones(n_types) if weights is None else weights,
                   dtype=float)
    w = w / w.sum()
    types = np.empty(n, dtype=np.int32)
    types[0] = rng.choice(n_types, p=w)
    redraw = rng.random(n) >= burstiness
    draws = rng.choice(n_types, size=n, p=w)
    for i in range(1, n):
        types[i] = draws[i] if redraw[i] else types[i - 1]
    return types


def generate(*, n_types: int, n_attrs: int, seed: int,
             events_per_minute: int = 200, minutes: int = 10,
             n_groups: int = 4, burstiness: float = 0.8,
             type_weights=None, attr_low: float = 0.0,
             attr_high: float = 10.0, ticks_per_minute: int = 60) -> dict:
    """``{type_id, time, attrs, group}`` arrays, time-sorted."""
    rng = np.random.default_rng(seed)
    n = events_per_minute * minutes
    types = markov_types(rng, n, n_types, type_weights, burstiness)
    total_ticks = minutes * ticks_per_minute
    if n <= total_ticks:
        times = np.sort(rng.choice(total_ticks, size=n, replace=False))
    else:
        times = np.sort(rng.integers(0, total_ticks, size=n))
    attrs = rng.uniform(attr_low, attr_high, size=(n, max(1, n_attrs)))
    groups = rng.integers(0, n_groups, size=n)
    return {"type_id": types, "time": np.asarray(times, dtype=np.int64),
            "attrs": attrs, "group": np.asarray(groups, dtype=np.int64)}
