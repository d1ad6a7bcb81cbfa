"""The reader of the planner's graphlet counters computes its share from a
hand-built window, and leaves the metric out when the program has no such
counters (a checkout from before them) or the window planned nothing."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

import cell


def ctx(counters):
    return SimpleNamespace(events=100_000.0, window_s=51.0,
                           counters=dict(counters), stats={},
                           submit_wait_ms=[], trace=None, traced_events=None)


def read(counters):
    return cell.load_module("metrics", "plan_stamp_share").read(ctx(counters))


@pytest.mark.parametrize("built, stamped", [(1_000, 0), (1_000, 800),
                                             (250, 250)])
def test_stamp_share_value(built, stamped):
    assert read({"engine.plan.graphlets": built,
                 "engine.plan.graphlets_stamped": stamped}) == pytest.approx(
        stamped / built)


@pytest.mark.parametrize("counters", [
    {},
    {"engine.plan.graphlets": 1_000},
    {"engine.plan.graphlets_stamped": 10},
    {"engine.plan.graphlets": 0, "engine.plan.graphlets_stamped": 0},
    {"serve.wait_s": 2.0, "host.gc_s": 1.0},
])
def test_stamp_share_left_out(counters):
    assert read(counters) is None
