"""The plain reference agrees with the program's float64 engine on both
deployment, and with the program's brute-force trend enumeration on
patterns with leading, middle and trailing NOT."""

from __future__ import annotations

import math

import numpy as np
import pytest

import cell
import program
import reference


def _max_rel(got, ref):
    assert got.keys() == ref.keys()
    worst = 0.0
    for k, rv in ref.items():
        assert got[k].keys() == rv.keys()
        for a, r in rv.items():
            g = got[k][a]
            if math.isnan(r) or math.isnan(g):
                assert math.isnan(r) and math.isnan(g), (k, a)
                continue
            worst = max(worst, abs(g - r) / abs(r) if r else abs(g))
    return worst


@pytest.mark.parametrize("config,minutes", [("smarthome_q25", 3)])
@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_matches_program_engine(config, minutes, seed):
    from repro.core.engine import HamletRuntime

    cfg = cell.load_json("configs", config)
    dep = cell.deployment(dict(cfg, minutes=minutes), seed)
    wl = program.workload(dep.specs, dep.types, dep.attrs)
    s = dep.stream
    t_end = (int(s["time"][-1]) + 1 + dep.pane - 1) // dep.pane * dep.pane
    got = HamletRuntime(wl, backend="np").run(
        program.event_batch(wl.schema, s), t_end=t_end)
    ref = reference.evaluate(s, dep.specs, dep.types, dep.attrs, t_end)
    assert _max_rel(got, ref) < 1e-12


NOT_SPECS = [
    dict(name="lead", pattern=["seq", ["not", ["type", "C"]], ["type", "A"],
                               ["kleene", ["type", "B"]]],
         aggs=[["count_star"], ["count_type", "B"]], preds={},
         within=8, slide=4, group_by=[]),
    dict(name="mid", pattern=["seq", ["type", "A"], ["not", ["type", "C"]],
                              ["kleene", ["type", "B"]]],
         aggs=[["count_star"], ["sum", "B", "x"], ["avg", "B", "x"]],
         preds={"C": [["x", "<", 5.0]]}, within=8, slide=4, group_by=[]),
    dict(name="trail", pattern=["seq", ["type", "A"],
                                ["kleene", ["type", "B"]],
                                ["not", ["type", "C"]]],
         aggs=[["count_star"]], preds={"B": [["x", ">", 2.0]]},
         within=8, slide=4, group_by=[]),
]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_negation_matches_brute_force(seed):
    from repro.core.baselines.brute import brute_run

    rng = np.random.default_rng(seed)
    n = 40
    s = {"type_id": rng.integers(0, 3, n).astype(np.int32),
         "time": np.sort(rng.integers(0, 24, n)).astype(np.int64),
         "attrs": rng.uniform(0, 10, (n, 1)),
         "group": rng.integers(0, 2, n).astype(np.int64)}
    types, attrs = ("A", "B", "C"), ("x",)
    wl = program.workload(NOT_SPECS, types, attrs)
    got = brute_run(wl, program.event_batch(wl.schema, s), t_end=24)
    ref = reference.evaluate(s, NOT_SPECS, types, attrs, 24)
    assert _max_rel(got, ref) < 1e-12
