"""The copied generators and query builders reproduce the program's, bit
for bit, at the cells' configurations and at seeds a check draws."""

from __future__ import annotations

import numpy as np
import pytest

import cell
import program

SEEDS = [1001, 2001, 2**31 + 12345]


def _program_stream(config, seed):
    from repro.core.events import StreamSchema
    from repro.streams import generator as G

    types, attrs = config["schema"]["types"], config["schema"]["attrs"]
    sch = StreamSchema(types=tuple(types), attrs=tuple(attrs))
    a = dict(config["stream"]["args"],
             ticks_per_minute=config["ticks_per_minute"],
             minutes=config["minutes"])
    if "type_weights" in a:
        a["type_weights"] = tuple(a["type_weights"])
    return G.bursty_stream(G.StreamConfig(schema=sch, seed=seed, **a))


@pytest.mark.parametrize("config", ["smarthome_q25"])
@pytest.mark.parametrize("seed", SEEDS)
def test_stream_copy_is_bitwise(config, seed):
    cfg = cell.load_json("configs", config)
    dep = cell.deployment(cfg, seed)
    ref = _program_stream(cfg, seed)
    for k in ("type_id", "time", "attrs", "group"):
        got, want = dep.stream[k], getattr(ref, k)
        assert got.dtype == want.dtype, k
        assert np.array_equal(got, want), k


def test_smarthome_copy_matches_named_stream():
    """The configuration is the program's ``smarthome_stream`` shape at
    the source's plug count and reading rate."""
    from repro.streams.generator import smarthome_stream

    cfg = cell.load_json("configs", "smarthome_q25")
    args = cfg["stream"]["args"]
    assert args["n_groups"] == cfg["houses"] * cfg["plugs_per_house"]
    assert args["events_per_minute"] == (args["n_groups"]
                                         * cfg["readings_per_plug_minute"])
    dep = cell.deployment(dict(cfg, minutes=2), 2)
    ref = smarthome_stream(events_per_minute=args["events_per_minute"],
                           n_groups=args["n_groups"], minutes=2, seed=2)
    for k in ("type_id", "time", "attrs", "group"):
        assert np.array_equal(dep.stream[k], getattr(ref, k)), k


def test_kleene_queries_match_program():
    from benchmarks.common import kleene_workload
    from repro.streams.generator import SMARTHOME_SCHEMA

    cfg = cell.load_json("configs", "smarthome_q25")
    dep = cell.deployment(dict(cfg, minutes=1), 0)
    got = program.workload(dep.specs, dep.types, dep.attrs)
    want = kleene_workload(SMARTHOME_SCHEMA, 25, kleene_type="Measure",
                           head_types=["Load", "Work", "Idle"], within=60,
                           slide=30, pred_attr="value")
    assert got.queries == want.queries
    assert got.schema == want.schema
