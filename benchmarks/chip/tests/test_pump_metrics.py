"""The readers of the program's pump counters compute their values from a
hand-built window, and leave the metric out when the program has no such
counter (a checkout from before the counters) or the window finished
nothing."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

import cell

COUNTERS = {"serve.seal_s": 0.5, "serve.route_s": 4.5, "serve.wait_s": 2.55,
            "engine.emit_s": 1.25, "kernels.compile_s": 0.0,
            "kernels.programs_built": 0, "host.gc_s": 3.0}


def ctx(counters=COUNTERS, events=100_000.0, window_s=51.0):
    return SimpleNamespace(events=events, window_s=window_s,
                           counters=dict(counters), stats={},
                           submit_wait_ms=[], trace=None, traced_events=None)


@pytest.mark.parametrize("name, want", [
    ("serve_ms_per_kev", (0.5 + 4.5) * 1e3 / 100.0),
    ("pump_wait_share", 2.55 / 51.0),
    ("emit_ms_per_kev", 1.25 * 1e3 / 100.0),
    ("compile_ms_per_kev", 0.0),
    ("gc_ms_per_kev", 3.0 * 1e3 / 100.0),
])
def test_reader_value(name, want):
    assert cell.load_module("metrics", name).read(ctx()) == pytest.approx(
        want)


@pytest.mark.parametrize("name", ["serve_ms_per_kev", "pump_wait_share",
                                  "emit_ms_per_kev", "compile_ms_per_kev",
                                  "gc_ms_per_kev"])
def test_reader_leaves_out_what_it_cannot_read(name):
    read = cell.load_module("metrics", name).read
    assert read(ctx(counters={})) is None
    assert read(ctx(events=0.0)) is None
    partial = {k: v for k, v in COUNTERS.items() if k != "serve.route_s"}
    if name == "serve_ms_per_kev":
        assert read(ctx(counters=partial)) is None
