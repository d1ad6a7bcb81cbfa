"""The readers of the sum round's counters compute their values from a
hand-built window, and leave the metric out when the program has no such
counters (a checkout from before them) or the window finished or submitted
nothing."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

import cell

COUNTERS = {"batch_exec.count_round_s": 1.5, "batch_exec.sum_round_s": 2.5,
            "batch_exec.jobs.count": 3_000, "batch_exec.jobs.sum": 1_000,
            "engine.plan.neg_steps": 40}


def ctx(counters=COUNTERS, events=100_000.0):
    return SimpleNamespace(events=events, window_s=51.0,
                           counters=dict(counters), stats={},
                           submit_wait_ms=[], trace=None, traced_events=None)


def read(name, c):
    return cell.load_module("metrics", name).read(c)


@pytest.mark.parametrize("name, want", [
    ("sum_round_ms_per_kev", 2.5 * 1e3 / 100.0),
    ("sum_job_share", 1_000 / 4_000),
])
def test_reader_value(name, want):
    assert read(name, ctx()) == pytest.approx(want)


def test_sum_job_share_zero_without_sum_units():
    assert read("sum_job_share", ctx(dict(COUNTERS, **{
        "batch_exec.jobs.sum": 0}))) == 0.0


@pytest.mark.parametrize("counters", [
    {},
    {"batch_exec.jobs.count": 3_000},
    {"batch_exec.jobs.sum": 10},
    {"batch_exec.jobs.count": 0, "batch_exec.jobs.sum": 0},
    {"serve.wait_s": 2.0, "host.gc_s": 1.0, "batch_exec.sum_round_s": 1.0},
])
def test_sum_job_share_left_out(counters):
    assert read("sum_job_share", ctx(counters)) is None


@pytest.mark.parametrize("c", [
    ctx(counters={}),
    ctx(counters={"batch_exec.count_round_s": 1.0}),
    ctx(events=0.0),
])
def test_sum_round_left_out(c):
    assert read("sum_round_ms_per_kev", c) is None
