"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a run (load generator process, sockets,
served path, drain, reference, comparison) on the CPU, skipping only the
look for a chip, with the engine on its numpy backend at a short window.
Faults: the fold step leaves the window state unchanged; half of every
sealed batch is left out; one answer is altered where it is produced.
The one-chip cells have no exchange between chips to leave out.
"""

from __future__ import annotations

import numpy as np
import pytest

import cell
import run

CELL = "smarthome_q25.closed"


def _run():
    cfg = cell.load_json("configs", "smarthome_q25")
    over = {"serve": dict(cfg["serve"], np_backend="np"), "minutes": 40,
            "warmup_panes": 8}
    return run.run(CELL, 20261016, 8.0, False, require_tpu=False,
                   overrides=over)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_state_left_unchanged(monkeypatch):
    import repro.overload.runtime as rt

    monkeypatch.setattr(rt, "advance_instances", lambda m, insts: None)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["max_rel_err"]["value"] > 1e-3


def test_half_the_batch_left_out(monkeypatch):
    from repro.serve import frontend

    ingest = frontend._OverloadBackend.ingest

    def half(self, chunk, boundary):
        if chunk is not None and len(chunk):
            chunk = chunk.select(np.arange(0, len(chunk), 2))
        return ingest(self, chunk, boundary)

    monkeypatch.setattr(frontend._OverloadBackend, "ingest", half)
    r = _run()
    assert not r["correct"]


def test_one_answer_altered(monkeypatch):
    from repro.serve import frontend
    from repro.serve.session import Delivery

    deliver = frontend.ServingFrontend._deliver
    done = []

    def alter(self, d, count=True):
        if not done and d.kind == "emit" and d.vals.get("COUNT(*)"):
            vals = dict(d.vals, **{"COUNT(*)": d.vals["COUNT(*)"] * 1.001})
            d = Delivery(d.kind, d.query, d.group, d.w0, vals, d.revision,
                         d.latency_ms)
            done.append(d)
        return deliver(self, d, count)

    monkeypatch.setattr(frontend.ServingFrontend, "_deliver", alter)
    r = _run()
    assert done
    assert not r["correct"]
    assert r["checks"]["max_rel_err"]["value"] == pytest.approx(1e-3,
                                                                rel=1e-6)
