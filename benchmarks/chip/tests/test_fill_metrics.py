"""The readers of the fold executor's snapshot-fill counters compute their
values from a hand-built window, and leave the metric out when the program
has no such counters (a checkout from before them) or the window finished
or folded nothing."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

import cell

COUNTERS = {"fold_exec.fill_s": 4.0, "fold_exec.fill.stacked": 190,
            "fold_exec.fill.sequential": 10, "batch_exec.sum_round_s": 2.5}


def ctx(counters=COUNTERS, events=100_000.0):
    return SimpleNamespace(events=events, window_s=51.0,
                           counters=dict(counters), stats={},
                           submit_wait_ms=[], trace=None, traced_events=None)


def read(name, c):
    return cell.load_module("metrics", name).read(c)


@pytest.mark.parametrize("name, counters, want", [
    ("fill_ms_per_kev", COUNTERS, 4.0 * 1e3 / 100.0),
    ("fill_ms_per_kev", {"fold_exec.fill_s": 0.0}, 0.0),
    ("fill_stacked_share", COUNTERS, 190 / 200),
    ("fill_stacked_share", {"fold_exec.fill.stacked": 5}, 1.0),
    ("fill_stacked_share", {"fold_exec.fill.sequential": 7}, 0.0),
    ("fill_stacked_share", {"fold_exec.fill.stacked": 0,
                            "fold_exec.fill.sequential": 3}, 0.0),
])
def test_reader_value(name, counters, want):
    assert read(name, ctx(counters)) == pytest.approx(want)


@pytest.mark.parametrize("c", [
    ctx(counters={}),
    ctx(counters={"batch_exec.sum_round_s": 1.0, "host.gc_s": 1.0}),
    ctx(events=0.0),
])
def test_fill_ms_left_out(c):
    assert read("fill_ms_per_kev", c) is None


@pytest.mark.parametrize("counters", [
    {},
    {"fold_exec.fill_s": 3.0, "batch_exec.jobs.sum": 10},
    {"fold_exec.fill.stacked": 0, "fold_exec.fill.sequential": 0},
])
def test_fill_stacked_share_left_out(counters):
    assert read("fill_stacked_share", ctx(counters)) is None
