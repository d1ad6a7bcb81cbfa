"""The trace reduction, on a small trace recorded on a TPU v5e
(``data/trace_v5e.json.gz``, made by ``record_trace.py``) and on
intervals built by hand."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

import devtrace

FIXTURE = Path(__file__).parent / "data" / "trace_v5e.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def test_union_and_clip():
    u = devtrace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert u == [(0, 2.5), (3, 4)]
    assert devtrace.clip(u, 1, 3.5) == [(1, 2.5), (3, 3.5)]


def test_reduce_by_hand():
    W = devtrace.WINDOW
    P, M, O = devtrace.DEVICE_PLANE, devtrace.MODULES_LINE, devtrace.OPS_LINE
    evs = [("/host:CPU", "main", W, 10.0, 2.0),
           (P, M, "jit_a", 9.5, 1.0),          # clipped to [10, 10.5]
           (P, M, "jit_b", 11.0, 0.25),
           (P, M, "jit_c", 11.1, 0.25),        # overlaps jit_b
           (P, O, "_propagate", 11.0, 0.1),
           (P, O, "copy", 11.2, 0.05),
           (P, M, "jit_d", 13.0, 1.0)]         # outside the window
    r = devtrace.reduce(evs)
    assert r["window_s"] == 2.0
    assert r["busy_s"] == pytest.approx(0.5 + 0.35)
    assert r["idle_share"] == pytest.approx(1 - 0.85 / 2)
    assert r["kernel_s"]["propagate"] == pytest.approx(0.1)
    assert sum(e - s for s, e in r["gaps"]) == pytest.approx(2 - 0.85)
    spans = [("plan", 10.6, 10.9), ("serve.flush", 10.0, 12.0)]
    gaps = devtrace.label_gaps(r["gaps"], spans, offset=0.0, top=2)
    assert gaps == [["serve.flush", pytest.approx(0.65)],
                    ["plan", pytest.approx(0.5)]]


def test_reduce_recorded_trace(recorded):
    r = devtrace.reduce(recorded)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert r["modules"] > 0
    assert r["kernel_s"]["propagate"] > 0
    idle = sum(e - s for s, e in r["gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert all(n and s > 0 for n, s in r["top_ops"])


def test_device_without_peaks_is_an_error():
    assert devtrace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        devtrace.peaks("cpu")
